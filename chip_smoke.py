"""Bring-up smoke of the inference-query engine on TPU.

Drives the main path once, through the public entry points, at published
table sizes: catalog -> MCTS optimizer -> QueryServer -> PlanCache ->
costed lowering -> physical operators and Pallas kernels. Every result is
checked against the logical-tree reference interpreter
(``repro.core.executor.execute_reference``) on the same data.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: sharded + partitioned only

One chip: serves ``analytics_q1`` (credit card, 289,000 x 29, a 100-tree
depth-9 forest) and ``rec_q1`` (6,000 users x 3,960 movies, two-tower
scoring over the cross join) through ``QueryServer(max_batch_size=4)``,
then serves one plan through each query-path Pallas kernel
(``decision_forest``, ``fused_dense``, ``block_matmul``) and checks that its
compiled executable holds the kernel (``tpu_custom_call``).

Four chips: the sharded micro-batch path (8 ``analytics_q1`` requests over
``data_mesh(4)``) and the partitioned oversized-query path (``rec_q1``
under a per-device memory budget), each compared with its one-device result
and with the reference.

The timings and memory printed on the way are bring-up observations, not
benchmark numbers. The last line of standard output is
``{"ok": true, "device": {...}}``; any failure exits non-zero without it:
no TPU, an unknown TPU kind, a failed or fallen-back request, a budget that
pruned every lowering candidate, a kernel not compiled for the chip, or a
result that differs from the reference.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

import numpy as np

SEED = 0
MCTS_ITERATIONS = 8
N_INSTANCES = 8
SCALES = {"analytics_q1": 100, "rec_q1": 60}
# what a compiled Pallas kernel leaves in the executable's HLO text
KERNEL_MARK = "tpu_custom_call"
# float tolerance of the CPU equality tests (tests/test_physical.py);
# valid-row sets and integer columns are compared exactly
RTOL = ATOL = 5e-4
# each kernel is reached by the rules that select it: (workload, [(rule,
# config filter)]) — R3-2/R3-1 make the relational node, R4-2 turns it
# 'fused' then 'pallas'; R4-1-fuse makes a fused_dense atom, R4-2 turns it
# 'pallas'
KERNEL_PLANS = {
    "decision_forest": ("analytics_q1", [
        ("R3-2", {}), ("R4-2", {"kind": "mode"}),
        ("R4-2", {"kind": "node", "backend": "pallas"})]),
    "fused_dense": ("rec_q1", [
        ("R4-1-fuse", {}), ("R4-2", {"kind": "atom", "backend": "pallas"})]),
    "block_matmul": ("rec_q1", [
        ("R4-1-split", {}), ("R3-1", {}), ("R4-2", {"kind": "mode"}),
        ("R4-2", {"kind": "node", "backend": "pallas"})]),
}


class SmokeFailure(Exception):
    pass


class Mismatch(SmokeFailure):
    """A result that differs from the reference."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# reference and comparison
# ---------------------------------------------------------------------------

def reference_plan(wl):
    """The workload's original query with exact-count compaction after its
    filters (rule ``compact``): without it rec_q1's reference would cross
    all 3,960 movies with every user, 23.8M rows, which no chip holds."""
    from repro.core.rules import ALL_RULES
    rule = ALL_RULES["compact"]
    plan = wl.plan
    while True:
        cfgs = rule.configs(plan, wl.catalog)
        if not cfgs:
            return plan
        plan = rule.apply(plan, wl.catalog, cfgs[0])


def reference_fn(wl):
    """``execute_reference`` over ``reference_plan`` for one payload, jitted
    so that XLA frees the interpreter's temporaries."""
    import jax
    from repro.core.executor import execute_reference

    plan = reference_plan(wl)

    def ref(tables):
        cat = copy.copy(wl.catalog)
        cat.tables = tables
        return execute_reference(plan, cat)

    return jax.jit(ref)


def compare(got, want, label: str) -> None:
    """Valid rows as a set (row order may differ between plans), integer
    columns exactly, float columns at the CPU tests' tolerance."""
    a, b = got.canonical(), want.canonical()
    if set(a) != set(b):
        raise Mismatch(f"{label}: columns {sorted(a)} != {sorted(b)}")
    na = len(next(iter(a.values())))
    nb = len(next(iter(b.values())))
    if na != nb:
        raise Mismatch(f"{label}: {na} valid rows, reference {nb}")
    for k in sorted(a):
        x, y = np.asarray(a[k]), np.asarray(b[k])
        if np.issubdtype(y.dtype, np.floating):
            bad = ~np.isclose(x, y, rtol=RTOL, atol=ATOL)
            if bad.any():
                err = np.abs(x.astype(np.float64) - y)
                raise Mismatch(
                    f"{label}:{k}: {int(bad.sum())} of {bad.size} values "
                    f"off (max abs err {err.max():.3e})")
        elif not np.array_equal(x, y):
            raise Mismatch(f"{label}:{k}: integer column differs "
                           f"({int((x != y).sum())} of {x.size})")


def check_server(server, reqs, label: str) -> dict:
    st = server.stats()
    errors = [r.error for r in reqs if r.error is not None]
    if st["failed"] or errors or not all(r.done for r in reqs):
        raise SmokeFailure(f"{label}: {st['failed']} failed request(s): "
                           f"{errors[:2]}")
    if st["fallbacks"]:
        raise SmokeFailure(f"{label}: fell back: {st['fallbacks']}")
    return st


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def log_device_peaks(label: str, devices) -> None:
    """Per-device peaks, to show that a mesh path spread its work: the
    one-device runs before it use device 0 only."""
    log(f"bring-up per-device peak_bytes_in_use after {label}: "
        + ", ".join(f"{d.id}:{peak_bytes(d)}" for d in devices))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def build(name: str, scale: float):
    from repro.core.planner import analytic_cost_fn, optimize_vanilla_mcts
    from repro.core.plan_cache import scan_table_names
    from repro.data import workloads

    wl = workloads.ALL_WORKLOADS[name](scale=scale, seed=SEED)
    t0 = time.perf_counter()
    plan, _ = optimize_vanilla_mcts(wl.plan, wl.catalog,
                                    cost_fn=analytic_cost_fn(wl.catalog),
                                    iterations=MCTS_ITERATIONS, seed=SEED)
    opt_s = time.perf_counter() - t0
    scanned = {k: wl.catalog.tables[k] for k in scan_table_names(wl.plan)}
    insts = workloads.rolled_instances(scanned, N_INSTANCES)
    return wl, plan, insts, opt_s


def serve_workload(name: str, scale: float, device) -> tuple:
    """Serve N_INSTANCES parameterized instances through
    QueryServer(max_batch_size=4): step() dispatches two vmapped
    micro-batches of 4, then instance 0 again alone takes the single-request
    path. Returns (workload, instances, jitted reference)."""
    import jax
    from repro.serving import QueryServer

    wl, plan, insts, opt_s = build(name, scale)
    rows = {t: wl.catalog.stats[t].rows for t in sorted(insts[0])}
    ref = reference_fn(wl)
    refs = [ref(t) for t in insts]
    jax.block_until_ready(refs)
    server = QueryServer(max_batch_size=4, max_wait_s=3600.0)
    reqs = [server.submit(plan, wl.catalog, t) for t in insts]
    server.step()
    single = server.submit(plan, wl.catalog, insts[0])
    server.drain()
    st = check_server(server, reqs + [single], name)
    for i, r in enumerate(reqs):
        compare(r.result, refs[i], f"{name}[{i}]")
    compare(single.result, refs[0], f"{name}[single]")
    if st["dispatches"] != 3 or single.batch_size != 1:
        raise SmokeFailure(f"{name}: expected 2 batches of 4 and 1 single, "
                           f"got {st['dispatches']} dispatches")
    cold = reqs[0].finish_t - reqs[0].dispatch_t
    warm = reqs[-1].finish_t - reqs[-1].dispatch_t
    log(f"bring-up {name}: rows {rows}, result rows "
        f"{int(refs[0].num_valid())}, plan {plan.signature()[:160]}")
    log(f"bring-up {name}: mcts {opt_s:.2f} s, first batch of 4 "
        f"{cold:.2f} s (compile included), warm batch of 4 "
        f"{warm * 1e3:.2f} ms, single (compile included) "
        f"{single.finish_t - single.dispatch_t:.2f} s, traces "
        f"{st['traces']}, peak_bytes_in_use so far {peak_bytes(device)}")
    return wl, insts, ref


def kernel_plan(wl, steps):
    from repro.core.rules import ALL_RULES
    plan = wl.plan
    for rule_name, want in steps:
        rule = ALL_RULES[rule_name]
        cfgs = [c for c in rule.configs(plan, wl.catalog)
                if all(c.get(k) == v for k, v in want.items())]
        if not cfgs:
            raise SmokeFailure(f"rule {rule_name} {want} does not apply to "
                               f"{plan.signature()[:120]}")
        plan = rule.apply(plan, wl.catalog, cfgs[0])
    return plan


def serve_kernel(kernel: str, wl, insts, ref, device) -> None:
    """Serve one plan that runs ``kernel`` and check that its executable
    holds the compiled kernel."""
    import jax
    from repro.serving import QueryServer

    plan = kernel_plan(wl, KERNEL_PLANS[kernel][1])
    server = QueryServer(max_batch_size=4, max_wait_s=3600.0)
    req = server.submit(plan, wl.catalog, insts[1])
    server.drain()
    check_server(server, [req], kernel)
    compare(req.result, ref(insts[1]), kernel)
    run = server.cache.get_or_compile(plan, wl.catalog, cache_key=req.key)
    t0 = time.perf_counter()
    text = run.lower(req.tables).compile().as_text()
    if KERNEL_MARK not in text:
        raise SmokeFailure(f"{kernel}: no {KERNEL_MARK} in the compiled "
                           f"executable (interpret mode?)")
    t1 = time.perf_counter()
    jax.block_until_ready(run(req.tables))
    warm = time.perf_counter() - t1
    log(f"bring-up kernel {kernel}: {wl.name} plan "
        f"{plan.signature()[:140]}; tpu_custom_call present; first dispatch "
        f"{req.finish_t - req.dispatch_t:.2f} s (compile included), "
        f"lower+compile again {t1 - t0:.2f} s, warm {warm * 1e3:.2f} ms, "
        f"peak_bytes_in_use so far {peak_bytes(device)}")


def one_chip(device) -> list:
    """Phases of the one-chip smoke, as (name, thunk) in run order."""
    served = {}

    def serve(name):
        served[name] = serve_workload(name, SCALES[name], device)

    def kernel(k):
        serve_kernel(k, *served[KERNEL_PLANS[k][0]], device)

    return ([(n, lambda n=n: serve(n)) for n in SCALES]
            + [(k, lambda k=k: kernel(k)) for k in KERNEL_PLANS])


def four_chips(devices) -> list:
    """The sharded batch path and the partitioned oversized-query path, each
    against its one-device result and the reference."""
    from repro.core.mesh import data_mesh

    mesh = data_mesh(4)
    return [("sharded", lambda: serve_sharded(mesh, devices)),
            ("partitioned", lambda: serve_partitioned(mesh, devices))]


def serve_sharded(mesh, devices) -> None:
    """8 analytics_q1 requests in 2 micro-batches of 4, the batch axis
    split over the mesh."""
    from repro.serving import QueryServer

    wl, plan, insts, _ = build("analytics_q1", SCALES["analytics_q1"])
    ref = reference_fn(wl)
    refs = [ref(t) for t in insts]
    one = QueryServer(max_batch_size=4, max_wait_s=3600.0)
    one_reqs = [one.submit(plan, wl.catalog, t) for t in insts]
    one.drain()
    check_server(one, one_reqs, "analytics_q1 one-device")
    srv = QueryServer(max_batch_size=4, max_wait_s=3600.0, mesh=mesh)
    reqs = [srv.submit(plan, wl.catalog, t) for t in insts]
    srv.drain()
    st = check_server(srv, reqs, "analytics_q1 sharded")
    if st["sharded_dispatches"] <= 0:
        raise SmokeFailure(f"analytics_q1: no sharded dispatch ({st})")
    for i, (r, o) in enumerate(zip(reqs, one_reqs)):
        compare(r.result, o.result, f"sharded[{i}] vs one device")
        compare(r.result, refs[i], f"sharded[{i}] vs reference")
    log(f"bring-up sharded: analytics_q1 {st['sharded_dispatches']} sharded "
        f"dispatches over {len(devices)} chips, warm batch of 4 "
        f"{(reqs[-1].finish_t - reqs[-1].dispatch_t) * 1e3:.2f} ms")
    log_device_peaks("sharded", devices)


def serve_partitioned(mesh, devices) -> None:
    """rec_q1 under a per-device budget between its partitioned and its
    one-device peak, so that its operators are partitioned over the mesh."""
    from repro.core import cost, costed_lowering
    from repro.serving import QueryServer

    wl, plan, insts, _ = build("rec_q1", SCALES["rec_q1"])
    insts = insts[:4]
    profile = cost.DeviceProfile.detect()
    single_peak = cost.plan_peak_memory(plan, wl.catalog, profile)
    part = costed_lowering.lower_costed(plan, wl.catalog, profile=profile,
                                        ways=4, memory_budget=single_peak
                                        * 0.999)
    if part.budget_pruned_all or part.plan.ways <= 1:
        raise SmokeFailure("rec_q1: no partitioned lowering under its "
                           "one-device peak")
    budget = 0.5 * (single_peak + part.peak_memory)
    ref = reference_fn(wl)
    refs = [ref(t) for t in insts]
    one = QueryServer(max_batch_size=1, max_wait_s=3600.0)
    one_reqs = [one.submit(plan, wl.catalog, t) for t in insts]
    one.drain()
    check_server(one, one_reqs, "rec_q1 one-device")
    srv = QueryServer(max_batch_size=4, max_wait_s=3600.0, mesh=mesh,
                      memory_budget=budget)
    reqs = [srv.submit(plan, wl.catalog, t) for t in insts]
    srv.drain()
    st = check_server(srv, reqs, "rec_q1 partitioned")
    if st["partitioned_dispatches"] <= 0 or not all(
            "#be=part" in r.key for r in reqs):
        raise SmokeFailure(f"rec_q1: not partitioned ({st})")
    for i, (r, o) in enumerate(zip(reqs, one_reqs)):
        compare(r.result, o.result, f"partitioned[{i}] vs one device")
        compare(r.result, refs[i], f"partitioned[{i}] vs reference")
    log(f"bring-up partitioned: rec_q1 one-device analytic peak "
        f"{single_peak:.4g} B, partitioned {part.peak_memory:.4g} B, budget "
        f"{budget:.4g} B, {st['partitioned_dispatches']} partitioned "
        f"dispatches, key {reqs[0].key.split('#be=', 1)[1][:120]}")
    log_device_peaks("partitioned", devices)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX's first device is {dev.platform!r}")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} TPUs, found "
            f"{len(devices)}")
        return 1
    log(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
        f"count {len(devices)}")

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core.cost import DeviceProfile
    from repro.data import movielens

    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def count(event, **_):
        for k in cache_events:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache_events[k] += 1

    jax.monitoring.register_event_listener(count)
    log(f"cost profile: {DeviceProfile.detect().name}; compile cache "
        f"{cache_dir}")
    cat = movielens.build(SCALES["rec_q1"], SEED)
    log(f"cut: rec_q1 at scale {SCALES['rec_q1']} has "
        f"{cat.stats['users'].rows} users, {cat.stats['movies'].rows} movies "
        f"and {cat.stats['ratings'].rows} ratings (MovieLens-1M: 6,040 users, "
        f"3,883 movies, 1,000,209 ratings)")
    del cat

    phases = four_chips(devices[:4]) if args.chips == 4 else one_chip(dev)
    t0 = time.perf_counter()
    for name, phase in phases:
        try:
            phase()
        except Mismatch as e:
            log(f"FAILED: {e}")
            # bf16 passes of float32 dots, or a bug? The same phase with
            # float32 matmuls tells them apart; the smoke still fails
            log(f"rerunning {name} under "
                f"jax.default_matmul_precision('float32')")
            try:
                with jax.default_matmul_precision("float32"):
                    phase()
                log(f"{name}: under float32 matmuls every result equals "
                    f"the reference")
            except SmokeFailure as e2:
                log(f"{name}: under float32 matmuls still FAILED: {e2}")
            return 1
        except SmokeFailure as e:
            log(f"FAILED: {e}")
            return 1
    log(f"smoke done in {time.perf_counter() - t0:.1f} s; compile cache "
        f"hits {cache_events['hits']}, misses {cache_events['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
