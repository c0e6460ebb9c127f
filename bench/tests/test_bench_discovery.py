"""A configuration, a traffic mix and a metric dropped in as new files,
with their entries in BENCHMARK.json, are found by name: no file that is
already there changes."""
import json
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _copy_checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_new_config_mix_and_metric_are_found(tmp_path, tiny, run_tiny,
                                             bench_run):
    root = _copy_checkout(tmp_path)
    configs = root / "bench" / "configs"
    # a configuration: a copy of the fraud files under a new name
    for suffix in (".json", ".py", "_reference.py"):
        shutil.copy(configs / f"creditcard_fraud{suffix}",
                    configs / f"fraud_copy{suffix}")
    # a traffic mix: two closed-loop clients in batches of 2
    mix = json.loads((root / "bench/traffic/closed8.json").read_text())
    mix.update(clients=2, max_batch_size=2, sample_steps=1)
    (root / "bench/traffic/closed2.json").write_text(json.dumps(mix))
    # a metric: dispatches per second of the window
    (root / "bench/metrics/dispatch_rate.closed2.py").write_text(
        "def read(rec):\n"
        "    return rec['completed'] / rec['occupancy'] / rec['window_s']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "fraud_copy", "source": "a test",
                             "file": "bench/configs/fraud_copy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "fraud.closed2",
                               "config": "fraud_copy", "traffic": "closed2",
                               "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "dispatch_rate.closed2",
                               "unit": "dispatches/s", "better": "higher",
                               "source": "program_counter",
                               "layer": "serving", "moves": "setup_s",
                               "workloads": ["fraud.closed2"]})
    # a metric split by cells, read by the reader of its first part
    bench["per_layer"].append({"name": "occupancy.closed2",
                               "unit": "requests/batch", "better": "higher",
                               "source": "program_counter",
                               "layer": "serving", "moves": "setup_s",
                               "workloads": ["fraud.closed2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = tiny("fraud.closed2", root=root)
    assert spec["traffic"]["clients"] == 2
    assert [m["name"] for m in spec["per_layer"]] == [
        "dispatch_rate.closed2", "occupancy.closed2"]
    rec = run_tiny(spec)
    assert rec["correct"] and rec["batch_sizes"] == {2: rec["completed"]}
    got = bench_run.read_metrics(spec, rec, "per_layer")
    assert got["dispatch_rate.closed2"]["value"] > 0
    assert got["dispatch_rate.closed2"]["unit"] == "dispatches/s"
    assert got["occupancy.closed2"]["value"] == 2.0
    assert not (root / "bench/metrics/occupancy.closed2.py").exists()


def test_open_loop_mix_runs_from_data(tmp_path, tiny, run_tiny):
    root = _copy_checkout(tmp_path)
    (root / "bench/traffic/closed1.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 40.0, "burst_every_s": 0.25,
        "burst_requests": 3, "schedule_seed": 1, "max_batch_size": 4,
        "max_wait_s": 0.002, "warm_steps": 1, "payloads": 4,
        "sample_steps": 3}))
    spec = tiny("fraud.closed1", root=root)
    rec = run_tiny(spec, seconds=1.0)
    assert rec["correct"], rec["checks"]
    assert rec["completed"] == rec["attempted"] > 0


def test_open_loop_schedule_ignores_the_seed(bench_run):
    t = {"loop": "open", "rate_per_s": 50.0, "schedule_seed": 4,
         "max_batch_size": 1}
    a = bench_run.Loop(None, None, None, [0], t).schedule(2.0)
    b = bench_run.Loop(None, None, None, [0], t).schedule(2.0)
    assert a == b and 50 < len(a) < 150 and a == sorted(a)


def test_no_chip_no_result():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "rec.closed1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_unknown_device_kind_is_refused(bench_run, monkeypatch, tmp_path):
    import jax
    import pytest

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(bench_run.NoChip, match="no peaks"):
        bench_run.find_chips(1)
