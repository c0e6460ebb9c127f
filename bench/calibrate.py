"""Readings that the limits of a cell's comparison are set from.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 3 \
        [--control-seeds 1,2,3]

Runs the cell once per seed in one process (set-up, a short window at the
cell's own load, the comparison of the sampled answers with the plain
reference), and, for each seed in ``--control-seeds``, also the control:
the reference computed in bfloat16, put in the program's place for the
same payloads. Prints one JSON line per seed with the program's numbers
and the control's. ``bench/run.py`` never runs the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    _, cell = bench.cell_entry(args.workload)
    try:
        devices, peaks = bench.find_chips(cell["chips"])
    except bench.NoChip as e:
        bench.log(f"no result: {e}")
        return 3
    sys.path.insert(0, str(bench.ROOT / "src"))
    spec = bench.load_cell(args.workload)
    bench.cache_everything()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = bench.run(spec, seed, args.seconds, devices=devices,
                        peaks=peaks, control=seed in control,
                        t_start=time.monotonic())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": rec["correct"],
            "program": {k: c["value"] for k, c in rec["checks"].items()},
            "control": rec.get("control"),
            "completed": rec["completed"], "window_s": rec["window_s"],
            "memory_peak_bytes": rec["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
