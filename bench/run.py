"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix in ``BENCHMARK.json``.
Everything that belongs to one of them is found by name:

- ``bench/configs/<config>.json``: the sizes, the limits of the comparison;
- ``bench/configs/<config>.py``: ``build`` (tables, weights and the query
  from the seed, on the device) and ``work`` (operations per query);
- ``bench/configs/<config>_reference.py``: the plain reference and the
  comparison (``reference``, ``compare``, ``live``, ``as_rows``);
- ``bench/traffic/<traffic>.json``: the loop and its parameters;
- ``bench/metrics/<metric>.py``: ``read(record)``, the value of one metric
  from the run's record, or ``None`` where the record has nothing to read.
  A metric split by the cells it moves (``mfu.closed1``, ``mfu.closed8``)
  falls back to the reader of its first part (``mfu.py``).

A run checks for a TPU whose kind is in ``bench/peaks.json`` before any
work, builds the data, lets the optimizer plan the query, warms up the
loop's own shapes, measures for ``--seconds``, then compares a sample of
the window's answers, drawn from the seed, with the plain reference. The
compared numbers go to standard error as its last lines; the last line of
standard output is the result as JSON. ``--trace 1`` records the window
under the JAX profiler and reports the per-layer metrics instead of the
end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from here to the window's start

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import NamedTuple  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPANS = ("submit", "step", "collect", "offer")


class NoChip(RuntimeError):
    """No TPU, too few chips, or a TPU kind without published peaks."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_entry(name: str, root: Path = ROOT) -> tuple:
    """``BENCHMARK.json`` and the cell's entry in it."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    return bench, cells[name]


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic and metrics, by name."""
    bench, cell = cell_entry(name, root)
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg_file = root / conf["file"]

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "cfg": json.loads(cfg_file.read_text()),
        "config": load_module(cfg_file.with_suffix(".py")),
        "reference": load_module(
            cfg_file.with_name(f"{cell['config']}_reference.py")),
        "traffic": json.loads(
            (root / "bench" / "traffic" / f"{cell['traffic']}.json")
            .read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "metrics_dir": root / "bench" / "metrics",
    }


def find_chips(n: int, peaks_file: Path = BENCH / "peaks.json"):
    """The first ``n`` TPU devices and their published peaks."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    peaks = json.loads(peaks_file.read_text())
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"no peaks for device kind {kind!r} in {peaks_file.name}")
    return devices[:n], peaks[kind]


def cache_everything() -> str:
    """The program's persistent compile cache, holding every program:
    JAX leaves out those that compile in under a second by default, and
    each run would compile them anew in its set-up."""
    import jax
    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------

class Done(NamedTuple):
    """A completed request, without its answer."""
    payload: int
    latency_s: float
    batch_size: int
    queue_wait_s: float
    error: str | None


class Reservoir:
    """A uniform sample of ``k`` loop steps, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen, self.kept = k, 0, []
        self.rng = np.random.default_rng(seed)

    def offer(self, item) -> None:
        if self.seen < self.k:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.kept[j] = item
        self.seen += 1


class Loop:
    """Drives ``QueryServer.submit``/``step`` as the traffic file says.

    closed: ``clients`` callers, each of which sends its next request when
    its last one has completed. A step submits every idle client's
    request, calls ``step()``, then collects what is done.

    open: requests are due on a schedule that depends on the traffic file
    alone (``rate_per_s``; every ``burst_every_s`` seconds
    ``burst_requests`` more at once; ``schedule_seed``), never on
    ``--seed``. Each is timed from when it was due.

    Request ``n`` carries payload ``n % len(payloads)``.
    """

    def __init__(self, server, plan, catalog, payloads, traffic):
        self.server, self.plan, self.catalog = server, plan, catalog
        self.payloads, self.t = payloads, traffic
        self.sent = 0
        self.outstanding = []  # (request, payload index, time it was due)
        if traffic["loop"] == "closed":
            if traffic["clients"] % traffic["max_batch_size"]:
                raise ValueError("a closed loop's clients must fill whole "
                                 "batches, or the last one waits forever")
        elif traffic["loop"] != "open":
            raise ValueError(f"unknown loop {traffic['loop']!r}")

    def schedule(self, seconds: float) -> list:
        """Due times of an open loop's requests, from the window's start."""
        rng = np.random.default_rng(self.t["schedule_seed"])
        gaps = rng.exponential(1.0 / self.t["rate_per_s"],
                               int(self.t["rate_per_s"] * seconds * 2) + 16)
        due = np.cumsum(gaps)
        every = self.t.get("burst_every_s")
        if every:
            bursts = np.repeat(np.arange(every, seconds, every),
                               self.t["burst_requests"])
            due = np.sort(np.concatenate([due, bursts]))
        return [float(x) for x in due[due < seconds]]

    def _submit(self, due: float) -> None:
        p = self.sent % len(self.payloads)
        req = self.server.submit(self.plan, self.catalog, self.payloads[p])
        self.outstanding.append((req, p, due))
        self.sent += 1

    def _collect(self, done: list) -> list:
        """Move completed requests to ``done`` as ``Done`` records, which
        hold no result: the caller gets ``(payload, result)`` of each and
        keeps only what the sample wants, so that the window's answers do
        not pile up on the device."""
        now = time.monotonic()
        left, answers = [], []
        for req, p, due in self.outstanding:
            if req.done:
                done.append(Done(p, now - due, req.batch_size,
                                 req.queue_wait_s, req.error))
                answers.append((p, req.result))
            else:
                left.append((req, p, due))
        self.outstanding = left
        return answers

    def _closed_step(self, done: list, marks: dict | None = None) -> list:
        import jax
        marks = {} if marks is None else marks
        t = time.monotonic()
        with jax.profiler.TraceAnnotation("submit"):
            for _ in range(self.t["clients"] - len(self.outstanding)):
                self._submit(t)
        marks["submit"], t = time.monotonic() - t, time.monotonic()
        with jax.profiler.TraceAnnotation("step"):
            self.server.step()
        marks["step"], t = time.monotonic() - t, time.monotonic()
        with jax.profiler.TraceAnnotation("collect"):
            answers = self._collect(done)
        marks["collect"] = time.monotonic() - t
        return answers

    def warm(self, steps: int) -> list:
        """Run the loop's own steps once through, so that every shape the
        window will use is compiled; returns their ``Done`` records."""
        done = []
        if self.t["loop"] == "closed":
            for _ in range(steps):
                self._closed_step(done)
        else:
            for _ in range(steps):
                for _ in range(self.t["max_batch_size"]):
                    self._submit(time.monotonic())
                self.server.step()
                self._collect(done)
            self.server.drain()
            self._collect(done)
        return done

    def window(self, seconds: float, sample: Reservoir,
               steps: StepLog | None = None) -> tuple:
        """Measure for ``seconds``; returns (``Done`` records of the
        completed requests, elapsed seconds). A closed loop's
        window ends when the step under way at ``seconds`` completes, so
        every request it counts lies wholly inside it; an open loop's ends
        when the last request due before ``seconds`` completes."""
        import jax
        done = []
        t0 = time.monotonic()
        if self.t["loop"] == "closed":
            own = steps is None
            steps = StepLog() if own else steps
            start = steps.now()
            while True:
                marks = {}
                answers = self._closed_step(done, marks)
                t = time.monotonic()
                with jax.profiler.TraceAnnotation("offer"):
                    sample.offer(answers)
                del answers
                marks["offer"] = time.monotonic() - t
                end = steps.now()
                steps.add(t0, start, marks, end)
                start = end
                if time.monotonic() - t0 >= seconds:
                    break
            if own:
                steps.close()
        else:
            due = [t0 + d for d in self.schedule(seconds)]
            i = 0
            while i < len(due) or self.outstanding:
                now = time.monotonic()
                with jax.profiler.TraceAnnotation("submit"):
                    while i < len(due) and due[i] <= now:
                        self._submit(due[i])
                        i += 1
                with jax.profiler.TraceAnnotation("step"):
                    if i == len(due):
                        self.server.drain()
                    else:
                        self.server.step()
                with jax.profiler.TraceAnnotation("collect"):
                    answers = self._collect(done)
                if answers:
                    sample.offer(answers)
                if i < len(due) and not self.outstanding:
                    time.sleep(max(0.0, due[i] - time.monotonic()))
        return done, time.monotonic() - t0


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def rows_of(table) -> dict:
    """A result table's valid rows, as numpy columns."""
    valid = np.asarray(table.valid)
    return {k: np.asarray(v)[valid] for k, v in table.columns.items()}


class CompileLog:
    """Compilation work by phase of a run: executables obtained (each
    compiled or read from the persistent cache) and the seconds that took,
    cache hits and the seconds of reading them, and the seconds of tracing
    and of lowering to XLA."""

    DURATIONS = {"/jax/core/compile/backend_compile_duration": "executables",
                 "/jax/compilation_cache/cache_retrieval_time_sec":
                     "cache_reads",
                 "/jax/core/compile/jaxpr_trace_duration": "traces",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings"}

    def __init__(self):
        import jax
        self.phase = None
        self.by_phase = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _add(self, kind, seconds):
        if self.phase is not None:
            d = self.by_phase.setdefault(self.phase, Counter())
            d[kind] += 1
            d[kind + "_s"] += seconds

    def _duration(self, event, duration, **kwargs):
        if event in self.DURATIONS:
            self._add(self.DURATIONS[event], duration)

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self._add("cache_hits", 0.0)

    def count(self, phase: str, kind: str = "executables") -> int:
        return int(self.by_phase.get(phase, {}).get(kind, 0))

    def summary(self, phase: str) -> str:
        d = self.by_phase.get(phase, Counter())
        return (f"{phase}: {int(d['executables'])} executables "
                f"({int(d['cache_hits'])} from the cache) in "
                f"{d['executables_s']:.3f} s, cache reads "
                f"{d['cache_reads_s']:.3f} s, tracing {d['traces_s']:.3f} s, "
                f"lowering {d['lowerings_s']:.3f} s")


class StepLog:
    """Where each step of a closed loop's window spent its time: the
    harness's phases on the host clock; the process's CPU seconds; the
    seconds of Python's collections (``gc.callbacks``); and how long a
    heartbeat thread, which wakes every 5 ms, went without waking. A step
    held up while its own thread waits shows CPU time far below its wall
    time and a heartbeat that kept beating; one held up because the whole
    process stood still shows the heartbeat stopped for as long."""

    FIELDS = ("wall", "cpu", "gc")
    BEAT_S, LATE_S = 0.005, 0.05

    def __init__(self):
        import threading
        self.steps, self.late = [], []
        self.gc_s, self._gc_t = 0.0, None
        gc.callbacks.append(self._gc)
        self._stop = threading.Event()
        self._beat = threading.Thread(target=self._heartbeat, daemon=True)
        self._beat.start()

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.monotonic()
        elif self._gc_t is not None:
            self.gc_s += time.monotonic() - self._gc_t
            self._gc_t = None

    def _heartbeat(self):
        t = time.monotonic()
        while not self._stop.wait(self.BEAT_S):
            now = time.monotonic()
            if now - t >= self.LATE_S:
                self.late.append((t, now))
            t = now

    def close(self):
        self._stop.set()
        self._beat.join()
        gc.callbacks.remove(self._gc)

    def now(self):
        return time.monotonic(), time.process_time(), self.gc_s

    def add(self, t0: float, start, marks: dict, end) -> None:
        stopped = sum(max(0.0, min(b, end[0]) - max(a, start[0]))
                      for a, b in self.late)
        self.steps.append({
            "at_s": start[0] - t0,
            **{f: b - a for f, a, b in zip(self.FIELDS, start, end)},
            "heartbeat_stopped": stopped, **marks})

    def slowest(self, n: int = 3) -> list:
        return sorted(self.steps, key=lambda x: -x["wall"])[:n]

    def report(self) -> list:
        if not self.steps:
            return []
        median = float(np.median([x["wall"] for x in self.steps]))
        lines = [f"steps: {len(self.steps)}, median {median * 1e3:.3f} ms, "
                 f"gc {sum(x['gc'] for x in self.steps):.3f} s in all; "
                 f"heartbeat late {len(self.late)} times"]
        for x in self.slowest():
            phases = ", ".join(f"{k} {x[k] * 1e3:.3f}" for k in
                               ("submit", "step", "collect", "offer") if k in x)
            lines.append(
                f"slow step at +{x['at_s']:.3f} s: {x['wall'] * 1e3:.3f} ms "
                f"({phases} ms), cpu {x['cpu'] * 1e3:.3f} ms, gc "
                f"{x['gc'] * 1e3:.3f} ms, heartbeat stopped {x['heartbeat_stopped'] * 1e3:.3f} ms")
        return lines


def run(spec: dict, seed: int, seconds: float, trace: bool = False,
        devices=None, peaks=None, control: bool = False,
        t_start: float | None = None) -> dict:
    """One run of a cell: set-up, window, comparison. Returns the record
    the metric readers read, with ``checks`` and ``correct``."""
    import jax
    from repro.core.planner import analytic_cost_fn, optimize_vanilla_mcts
    from repro.serving import QueryServer

    t_start = T_START if t_start is None else t_start
    cfg, traffic = spec["cfg"], spec["traffic"]
    devices = devices or jax.devices()[:spec["cell"]["chips"]]
    compiles = CompileLog()

    t0 = time.monotonic()
    compiles.phase = "build"
    built = spec["config"].build(cfg, seed, traffic["payloads"])
    jax.block_until_ready(built["raw"])
    t1 = time.monotonic()
    compiles.phase = "optimize"
    plan, _ = optimize_vanilla_mcts(
        built["plan"], built["catalog"],
        cost_fn=analytic_cost_fn(built["catalog"]),
        iterations=cfg["mcts_iterations"], seed=cfg["mcts_seed"])
    t2 = time.monotonic()
    log(f"plan: {plan.signature()}")
    server = QueryServer(max_batch_size=traffic["max_batch_size"],
                         max_wait_s=traffic["max_wait_s"])
    loop = Loop(server, plan, built["catalog"], built["payloads"], traffic)
    compiles.phase = "warm-up"
    warm = loop.warm(traffic["warm_steps"])
    warm_sizes = Counter(d.batch_size for d in warm)
    del warm
    t3 = time.monotonic()

    before = server.stats()
    sample = Reservoir(traffic["sample_steps"], seed)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        # no Python-call tracer: it would slow the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level = 0, 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    compiles.phase = "window"
    steps = StepLog()
    t_window = time.monotonic()
    with jax.profiler.TraceAnnotation("window"):
        done, window_s = loop.window(seconds, sample, steps)
    compiles.phase = None
    steps.close()
    if trace:
        jax.profiler.stop_trace()
    after = server.stats()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    # requests sent in the window: those that never completed count failed
    attempted = len(done) + len(loop.outstanding)
    failed = sum(1 for d in done if d.error is not None)
    failed += len(loop.outstanding)
    sizes = Counter(d.batch_size for d in done)
    lat = np.asarray([d.latency_s for d in done], np.float64)
    qwait = np.asarray([d.queue_wait_s for d in done], np.float64)
    dispatches = after["dispatches"] - before["dispatches"]
    rec = {
        "seed": seed,
        "setup_s": t_window - t_start, "start_s": t0 - t_start,
        "build_s": t1 - t0, "optimize_s": t2 - t1, "warmup_s": t3 - t2,
        "window_s": window_s, "completed": attempted - failed,
        "attempted": attempted, "failed": failed,
        "latencies_s": lat.tolist(),
        "queue_wait_s": qwait.tolist(),
        "occupancy": ((after["completed"] - before["completed"]) / dispatches
                      if dispatches else None),
        "batch_sizes": dict(sorted(sizes.items())),
        "compiles_in_window": compiles.count("window"),
        "slowest_steps": steps.slowest(),
        "traces_in_window": after["traces"] - before["traces"],
        "memory_peak_bytes": peak,
        "peaks": peaks,
        "chips": len(devices),
        "trace": None,
    }
    log(f"set-up: start {rec['start_s']:.3f} s, build {rec['build_s']:.3f} s, optimize "
        f"{rec['optimize_s']:.3f} s, warm-up {rec['warmup_s']:.3f} s "
        f"(batch sizes {dict(warm_sizes)}), set-up {rec['setup_s']:.3f} s")
    pct = (np.percentile(lat * 1e3, [50, 95, 99]) if lat.size
           else [math.nan] * 3)
    log(f"window: {len(done)} requests in {window_s:.3f} s, {dispatches} "
        f"dispatches, batch sizes {rec['batch_sizes']}, compiles in window "
        f"{rec['compiles_in_window']}, traces in window "
        f"{rec['traces_in_window']}, peak_bytes_in_use {peak}")
    log(f"latency ms: p50 {pct[0]:.3f}, p95 {pct[1]:.3f}, p99 {pct[2]:.3f}, "
        f"max {lat.max() * 1e3 if lat.size else math.nan:.3f}")
    for phase in ("build", "optimize", "warm-up", "window"):
        log("compiles in " + compiles.summary(phase))
    for line in steps.report():
        log(line)

    # the sample leaves the device; then the program's state is freed, so
    # that the reference runs on an empty chip
    got = [(p, rows_of(res)) for step in sample.kept for p, res in step
           if res is not None]
    del built["payloads"], built["plan"], built["catalog"]
    del loop, server, done, sample, plan
    gc.collect()

    t4 = time.monotonic()
    refs, numbers, control_numbers = {}, {}, {}
    ref_mod = spec["reference"]
    for p, rows in got:
        if p not in refs:
            refs[p] = ref_mod.reference(cfg, built["raw"][p],
                                        built["weights"], seed)
            if hasattr(ref_mod, "describe"):
                log(f"payload {p}: {ref_mod.describe(cfg, refs[p])}")
        for k, v in ref_mod.compare(cfg, rows, refs[p]).items():
            numbers[k] = max(numbers.get(k, -math.inf), v)
    if control:
        for p in refs:
            low = ref_mod.reference(cfg, built["raw"][p], built["weights"],
                                    seed, control=True)
            rows = ref_mod.as_rows(cfg, low)
            for k, v in ref_mod.compare(cfg, rows, refs[p]).items():
                control_numbers[k] = max(control_numbers.get(k, -math.inf), v)
    live = ref_mod.live(cfg, next(iter(refs.values()))) if refs else None
    rec["work"] = spec["config"].work(cfg, live) if refs else None
    log(f"reference: {len(got)} answers of {len(refs)} payloads compared in "
        f"{time.monotonic() - t4:.3f} s; live rows {live}")

    limits = cfg["limits"]
    values = {"failed": float(failed), **numbers}
    if not got:
        values["compared"] = 0.0
        limits = {**limits, "compared": -1}
    checks = {k: {"value": values.get(k, math.inf), "limit": limits[k]}
              for k in limits}
    rec["checks"] = checks
    rec["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    if control:
        rec["control"] = control_numbers

    if trace:
        rec["trace"] = load_module(BENCH / "trace.py").read(
            log_dir, SPANS, len(devices))
        shutil.rmtree(log_dir, ignore_errors=True)
    return rec


def metric_reader(metrics_dir: Path, name: str):
    """``<name>.py``, else the reader of the name's first part."""
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        path = metrics_dir / f"{name.split('.', 1)[0]}.py"
    return load_module(path)


def read_metrics(spec: dict, rec: dict, which: str) -> dict:
    out = {}
    for m in spec[which]:
        value = metric_reader(spec["metrics_dir"], m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(spec: dict, rec: dict, devices, trace: bool) -> dict:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"],
           "metrics": read_metrics(spec, rec,
                                   "per_layer" if trace else "end_to_end"),
           "device": device}
    if trace:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        top = load_module(BENCH / "trace.py").top
        out["breakdown"] = {"device_ops": top(rec["trace"]["ops"]),
                            "idle_gaps": top(rec["trace"]["idle_gaps"])}
    out["checks"] = rec["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    _, cell = cell_entry(args.workload)
    try:
        devices, peaks = find_chips(cell["chips"])
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    spec = load_cell(args.workload)
    log(f"compile cache: {cache_everything()}")
    rec = run(spec, args.seed, args.seconds, trace=bool(args.trace),
              devices=devices, peaks=peaks)
    line = result_line(spec, rec, devices, bool(args.trace))
    for name, c in rec["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
