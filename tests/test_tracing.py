"""Spans and operator scopes: the serving tier's and the optimizer's host
spans land in the profiler's trace with their ids and nesting, every op of
a compiled plan executable carries the physical operator that ran it, and
the search publishes each cost call's duration on JAX's monitoring bus."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.core import mesh as mesh_util
from repro.core.mcts import VanillaMCTS
from repro.core.plan_cache import PlanCache
from repro.core.planner import analytic_cost_fn, optimize_vanilla_mcts
from repro.data import workloads
from repro.serving import QueryServer

SCALE = 0.25
SPANS = ("serving.submit", "serving.dispatch", "executor.lookup",
         "executor.launch", "executor.wait", "optimizer.search",
         "optimizer.rewrite", "optimizer.cost")
# ops that hold or pass data and run no operator's work
EXEMPT = ("parameter", "constant", "tuple", "get-tuple-element")


def _spans(log_dir):
    """``[(line, (name, start_ns, end_ns, ids))]`` of the program's spans
    on the host plane."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    assert len(found) == 1
    profile = ProfileData.from_file(str(found[0]))
    plane = next(p for p in profile.planes if p.name == "/host:CPU")
    return [(line.name, (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
            for line in plane.lines for e in line.events if e.name in SPANS]


def test_served_query_leaves_its_spans_in_the_trace(tmp_path):
    w = workloads.rec_q1(scale=SCALE)
    payloads = workloads.rolled_instances(dict(w.catalog.tables), 3)
    server = QueryServer(max_batch_size=2, max_wait_s=3600.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan, _ = optimize_vanilla_mcts(w.plan, w.catalog, iterations=3,
                                        seed=0)
        reqs = [server.submit(plan, w.catalog, t) for t in payloads]
        server.step()   # the full batch of 2
        server.drain()  # the third request alone
    finally:
        jax.profiler.stop_trace()
    assert all(r.done and r.error is None for r in reqs)

    ours = _spans(tmp_path)
    assert {e[0] for _, e in ours} == set(SPANS)

    submits = [e for _, e in ours if e[0] == "serving.submit"]
    assert sorted(e[3]["rid"] for e in submits) == [r.rid for r in reqs]
    dispatches = [(line, e) for line, e in ours
                  if e[0] == "serving.dispatch"]
    assert sorted((e[3]["rid0"], e[3]["size"]) for _, e in dispatches) == [
        (reqs[0].rid, 2), (reqs[2].rid, 1)]
    for line, (name, a, b, _) in ours:
        if name.startswith("executor."):
            assert any(l2 == line and a2 <= a and b <= b2
                       for l2, (_, a2, b2, _) in dispatches), name
    for name in ("executor.lookup", "executor.launch", "executor.wait"):
        assert sum(e[0] == name for _, e in ours) == len(dispatches)


def _executables(name):
    """The single, batched (B=4) and sharded (B=4, a one-device mesh)
    executables of a workload, each called once."""
    w = workloads.ALL_WORKLOADS[name](scale=SCALE)
    tabs = workloads.rolled_instances(dict(w.catalog.tables), 4)
    cache = PlanCache()
    low = cache._lowered_for(w.plan, w.catalog,
                             cache.base_key(w.plan, w.catalog), "sharded")
    mesh = mesh_util.data_mesh(1)
    runs = {
        "jit_plan_single": (cache.get_or_compile(w.plan, w.catalog),
                            tabs[0]),
        "jit_plan_batched4": (cache.get_or_compile_batched(
            w.plan, w.catalog, 4), tuple(tabs)),
        "jit_plan_sharded4": (cache._get_or_compile_stacked(
            "sharded-test", low.plan, w.plan, w.catalog, 4, kind="sharded",
            wrap=lambda body: mesh_util.shard_batch(body, mesh)),
            tuple(tabs)),
    }
    out = {}
    for module, (run, args) in runs.items():
        jax.block_until_ready(run(args))
        out[module] = tracing.executables()[-1]
    return out


@pytest.mark.parametrize("name", ["rec_q1", "analytics_q1"])
def test_every_op_of_an_executable_carries_its_operator(name):
    for module, exe in _executables(name).items():
        assert exe.module == module
        unscoped = {op: s.opcode for op, s in exe.ops.items()
                    if s.opcode not in EXEMPT
                    and s.operator not in tracing.OPERATORS}
        assert not unscoped, (module, unscoped)


@pytest.mark.parametrize("op_name,operator", [
    ("jit(plan_single)/aggregate/mul", "aggregate"),
    ("jit(plan_batched4)/vmap(aggregate)/mul", "aggregate"),
    ("jit(f)/cross_join/jit(_where)/select_n", "cross_join"),
    ("jit(f)/shard_map/vmap(project)/dot_general", "project"),
    ("jit(f)/project/aggregate", "project"),  # the primitive is no scope
    ("while/body/reduce_sum", None),
    ("x", None),
])
def test_operator_of_an_op_name(op_name, operator):
    assert tracing.operator_of(op_name) == operator


def test_a_fusion_across_operators_is_marked_and_named_after_its_root():
    def plan_body(x):
        with jax.named_scope("aggregate"):
            y = jnp.sin(x) * 2
        with jax.named_scope("project"):
            return jnp.tanh(y)

    text = jax.jit(plan_body).lower(jnp.ones(64)).compile().as_text()
    fusions = [s for s in tracing.operators(text).values()
               if s.opcode == "fusion"]
    assert fusions and all(s.cross and s.operator == "project"
                           for s in fusions)


def test_search_publishes_each_cost_call_on_the_monitoring_bus():
    w = workloads.analytics_q1(scale=SCALE)
    cost = analytic_cost_fn(w.catalog)
    calls, seen = [], []

    def counted(plan):
        calls.append(1)
        return cost(plan)

    def listen(event, duration, **kwargs):
        if event == "/repro/optimizer.cost":
            seen.append(duration)

    before = tracing.published_s["optimizer.cost"]
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        VanillaMCTS(w.catalog, counted, iterations=4, seed=0).optimize(w.plan)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert calls and len(seen) == len(calls)
    assert tracing.published_s["optimizer.cost"] - before == pytest.approx(
        sum(seen))


def test_an_executable_records_its_device_bytes_beside_the_analytic_peak():
    w = workloads.rec_q1(scale=SCALE)
    cache = PlanCache()
    cache.get_or_compile(w.plan, w.catalog)(dict(w.catalog.tables))
    key = cache.key(w.plan, w.catalog)
    exe = tracing._EXECUTABLES[key]
    low = cache._lowered_for(w.plan, w.catalog, cache.base_key(w.plan,
                                                               w.catalog),
                             None)
    assert exe.analytic_peak_bytes == low.peak_memory > 0
    assert exe.argument_bytes > 0 and exe.output_bytes > 0
    assert exe.temp_bytes >= 0


def test_budget_counters_move_when_a_budget_is_below_a_plan_peak():
    from repro.core import cost, costed_lowering
    w = workloads.rec_q1(scale=SCALE)
    profile = cost.DeviceProfile.detect()
    peak = cost.plan_peak_memory(w.plan, w.catalog, profile)
    pruned, demoted = (tracing.counts["lowering.pruned"],
                       tracing.counts["cost.demoted"])
    cost.plan_cost(w.plan, w.catalog, profile, memory_budget=peak * 2)
    assert tracing.counts["cost.demoted"] == demoted
    fits = cost.plan_cost(w.plan, w.catalog, profile, memory_budget=peak * 2)
    over = cost.plan_cost(w.plan, w.catalog, profile, memory_budget=peak / 2)
    assert tracing.counts["cost.demoted"] == demoted + 1
    assert over == pytest.approx(fits * cost.OVER_BUDGET * 2)
    costed_lowering.lower_costed(w.plan, w.catalog, profile=profile,
                                 memory_budget=64.0)
    assert tracing.counts["lowering.pruned"] > pruned
    stats = QueryServer().stats()["budget"]
    assert stats == {"lowering.pruned": tracing.counts["lowering.pruned"],
                     "cost.demoted": tracing.counts["cost.demoted"]}
