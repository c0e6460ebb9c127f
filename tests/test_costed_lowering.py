"""Costed lowering: equality with the reference on all 12 workloads,
strictly cheaper plans where the oracle finds them, one shared plan_cost
entry point across MCTS and lower(), and calibration-driven re-lowering
without stale-executable aliasing in the PlanCache."""
import dataclasses
import functools
import hashlib

import numpy as np
import pytest

from repro.core import cost, costed_lowering, executor, ir, planner, stage_graph
from repro.core import physical as ph
from repro.core.lowering import lower
from repro.core.mcts import VanillaMCTS
from repro.core.plan_cache import PlanCache
from repro.core.rules import base as rules_base
from repro.data import workloads
from repro.serving import feedback

SCALE = 0.5


def assert_tables_equal(ref, out, label):
    """Masks/integer columns exact; floats to the established 2e-5 vmap
    tolerance (canonicalized: valid rows only, order-independent)."""
    assert set(ref) == set(out), f"{label}: schema {sorted(set(ref) ^ set(out))}"
    for k in ref:
        a, b = ref[k], out[k]
        assert a.shape == b.shape, f"{label}:{k} {a.shape} vs {b.shape}"
        if np.issubdtype(a.dtype, np.integer) or a.dtype == bool:
            np.testing.assert_array_equal(a, b, err_msg=f"{label}:{k}")
        else:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{label}:{k}")


# ---------------------------------------------------------------------------
# equality + strictly-cheaper (acceptance criteria)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(workloads.ALL_WORKLOADS))
def test_costed_lowering_equals_reference(name):
    w = workloads.ALL_WORKLOADS[name](scale=SCALE)
    ref = executor.execute_reference(w.plan, w.catalog).canonical()
    out = ph.run(lower(w.plan, w.catalog), dict(w.catalog.tables)).canonical()
    assert_tables_equal(ref, out, name)


def test_costed_lowering_strictly_cheaper_on_some_workloads():
    """The oracle must find a strictly cheaper realization than tree-order
    lowering on at least 3 of the 12 workloads (compaction insertion after
    the selective ML filters is the main win at this scale)."""
    profile = cost.DeviceProfile.detect()
    cheaper = []
    for name in sorted(workloads.ALL_WORKLOADS):
        w = workloads.ALL_WORKLOADS[name](scale=SCALE)
        c_tree = cost.plan_cost(lower(w.plan, w.catalog, costed=False),
                                w.catalog, profile)
        c_best = cost.plan_cost(lower(w.plan, w.catalog, profile=profile),
                                w.catalog, profile)
        assert c_best <= c_tree * (1 + 1e-12), name  # never worse
        if c_best < c_tree * (1 - 1e-9):
            cheaper.append(name)
    assert len(cheaper) >= 3, cheaper


def test_default_decisions_reproduce_tree_order_lowering():
    """realize(default_decisions) must be the exact tree-order physical
    plan: same signature, same analytic cost (the candidate baseline)."""
    for name in ("rec_q1", "analytics_q1", "simple_q3"):
        w = workloads.ALL_WORKLOADS[name](scale=0.3)
        g = stage_graph.build(w.plan, w.catalog,
                              profile=cost.DeviceProfile.detect())
        tree = lower(w.plan, w.catalog, costed=False)
        assert g.realize(g.default_decisions()).signature() == tree.signature()


def test_backend_override_wins_over_cost_choice():
    """A caller's backend override restricts every realization candidate —
    the caller's kernel choice is sovereign over the oracle's."""
    from repro.core.rules import ALL_RULES

    w = workloads.analytics_q1(scale=0.3)
    cfgs = ALL_RULES["R3-2"].configs(w.plan, w.catalog)
    assert cfgs, "R3-2 must apply to the forest workload"
    plan = ALL_RULES["R3-2"].apply(w.plan, w.catalog, cfgs[0])
    for be in ("jnp", "sharded"):  # plan-level 'sharded' resolves to jnp
        pplan = lower(plan, w.catalog, backend=be)
        seen = 0
        for node in _walk_phys(pplan.root):
            if isinstance(node, (ph.PBlockedMatmul, ph.PForestRelational)):
                assert node.backend == "jnp"
                seen += 1
        assert seen >= 1


def _walk_phys(node):
    yield node
    for c in node.children():
        yield from _walk_phys(c)


# ---------------------------------------------------------------------------
# one shared plan_cost entry point (MCTS + lower)
# ---------------------------------------------------------------------------

def test_mcts_and_lowering_share_the_plan_cost_oracle(monkeypatch):
    calls = {"n": 0}
    real = cost.plan_cost

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(cost, "plan_cost", counting)
    w = workloads.rec_q1(scale=0.3)  # has open sites: >1 candidate scored
    # costed lowering scores its candidates through cost.plan_cost
    costed_lowering.lower_costed(w.plan, w.catalog)
    lowering_calls = calls["n"]
    assert lowering_calls > 1
    # the MCTS default reward oracle is the same entry point
    m = VanillaMCTS(w.catalog, iterations=2, seed=0)
    m.optimize(w.plan)
    assert calls["n"] > lowering_calls


# Each workload's logical plan at scale 0.3 under ``cost.TPU_PROFILE``:
# (plan_cost, plan_peak_memory, batched_plan_cost at B=8 with ways 1 and 4,
# plan_cost_breakdown's fields, sha256[:16] of the signature of the plan
# optimize_vanilla_mcts(iterations=20, seed=0) chooses, with the rules'
# fresh-column counter at zero). Recorded when the oracle had a separate
# walk over logical plans; the tree-order walk must reproduce them.
_PINNED = {
    "analytics_q1": (
        7.164180708180708e-06, 620536.0, 1.006900122100122e-05,
        1.1579155067155065e-05,
        (3122934.0, 339864, 613600.0, 0, 3, 7.164180708180708e-06, 0.0),
        "0bb2a4466e9637a5"),
    "analytics_q2": (
        1.1796454212454212e-05, 250272, 2.4319189255189254e-05,
        1.758541636141636e-05,
        (26350.0, 1465160, 6136.0, 0, 5, 1.1796454212454212e-05, 0.0),
        "354217ae85b4b586"),
    "analytics_q3": (
        1.5226422466422464e-05, 615280.0, 3.256693528693529e-05,
        2.1703638583638583e-05,
        (779460.0, 2028840, 613600.0, 0, 6, 1.5226422466422466e-05, 0.0),
        "230586dfed7d67cf"),
    "rec_q1": (
        1.3753811965811966e-05, 1756032.0, 2.5264456992122374e-05,
        1.931611424803059e-05,
        (164720990.0, 1010548.0, 425824.0, 0, 6, 1.3753811965811965e-05, 0.0),
        "2c20200b29f5a11c"),
    "rec_q2": (
        0.00013131704813224002, 55726976.0, 0.0009649106414681765,
        0.00025440184718023093,
        (13786884936.0, 54590496.0, 35851012.0, 0, 6, 0.00013131704813224,
         0.0),
        "b28db48025407925"),
    "rec_q3": (
        0.00010607038827838827, 36275296.0, 0.00015047818315018315,
        0.00011641435897435897,
        (1008331464.0, 5195712.0, 71847936.0, 0, 6, 0.00010607038827838827,
         0.0),
        "76cc3fbb6a73bf20"),
    "retail_q1": (
        8.401470085470085e-06, 128324.0, 1.1050358974358974e-05,
        1.2779882783882782e-05,
        (2689696.0, 309920, 18884.0, 0, 4, 8.401470085470085e-06, 0.0),
        "b4c5127994c1257e"),
    "retail_q2": (
        1.4552727716727717e-05, 173996.0, 1.729631746031746e-05,
        1.8944669108669106e-05,
        (2430960.0, 321000, 131684.0, 0, 7, 1.4552727716727717e-05, 0.0),
        "990562b568a5bbc4"),
    "retail_q3": (
        1.1219907203907204e-05, 1052336.0, 1.9155907203907203e-05,
        1.635362148962149e-05,
        (50975400.0, 928512.0, 70592.0, 0, 5, 1.1219907203907204e-05, 0.0),
        "72141c2eb9290c98"),
    "simple_q1": (
        2.0385641025641026e-06, 27264.0, 2.1247179487179486e-06,
        6.050871794871795e-06,
        (92160.0, 10080, 21504.0, 0, 1, 2.0385641025641026e-06, 0.0),
        "4a163320b2200f95"),
    "simple_q2": (
        4.263052503052503e-06, 66240, 5.779633699633699e-06,
        8.47970695970696e-06,
        (295936.0, 177440, 38000.0, 0, 2, 4.263052503052503e-06, 0.0),
        "3f4afcf53cc7fefa"),
    "simple_q3": (
        4.071868131868132e-06, 19440, 4.574432234432234e-06,
        8.143663003663004e-06,
        (22440.0, 58800, 60.0, 0, 2, 4.071868131868132e-06, 0.0),
        "8c1e9bf2816bc262"),
}


@functools.lru_cache(maxsize=None)
def _pinned_workload(name):
    return workloads.ALL_WORKLOADS[name](scale=0.3)


@pytest.mark.parametrize("check", ["costs", "mcts_plan"])
@pytest.mark.parametrize("name", sorted(_PINNED))
def test_oracle_numbers_are_pinned(name, check, monkeypatch):
    """A logical plan costs what it always has: the oracle's time, memory,
    breakdown and batched numbers, and the plan MCTS chooses by them."""
    w = _pinned_workload(name)
    prof = cost.TPU_PROFILE
    c, peak, b8, b8w4, fields, sig = _PINNED[name]
    if check == "mcts_plan":
        # rewrites name new columns from a process-wide counter, and the
        # names are part of the signature
        monkeypatch.setattr(rules_base, "_fresh_counter", [0])
        best, _ = planner.optimize_vanilla_mcts(
            w.plan, w.catalog, iterations=20, seed=0,
            cost_fn=planner.analytic_cost_fn(w.catalog, prof))
        got = best.signature()
        assert hashlib.sha256(got.encode()).hexdigest()[:16] == sig, got
        return
    assert cost.plan_cost(w.plan, w.catalog, prof) == pytest.approx(
        c, rel=1e-12)
    assert cost.plan_peak_memory(w.plan, w.catalog, prof) == pytest.approx(
        peak, rel=1e-12)
    assert cost.batched_plan_cost(w.plan, w.catalog, 8, prof) \
        == pytest.approx(b8, rel=1e-12)
    assert cost.batched_plan_cost(w.plan, w.catalog, 8, prof, ways=4) \
        == pytest.approx(b8w4, rel=1e-12)
    b = cost.plan_cost_breakdown(w.plan, w.catalog, prof)
    got = (b.flops, b.hbm_bytes, b.param_bytes, b.vmem_bytes, b.n_ops,
           b.seconds, b.n_coll)
    assert got == pytest.approx(fields, rel=1e-12)


# ---------------------------------------------------------------------------
# decision vector in PlanCache keys + calibration-driven re-lowering
# ---------------------------------------------------------------------------

def test_plan_cache_key_reflects_realization_vector():
    w = workloads.rec_q2(scale=SCALE)
    cache = PlanCache()
    key = cache.key(w.plan, w.catalog)
    assert "#cl=" in key
    low = costed_lowering.lower_costed(w.plan, w.catalog,
                                       profile=cache.profile)
    assert key.endswith("#cl=" + low.signature)


def _true_device_exports(prior):
    """Measurements a dispatch-overhead-heavy, high-bandwidth device would
    produce (deterministic: linearized predictions of a synthetic profile)."""
    true = dataclasses.replace(prior, op_overhead_s=5e-4, hbm_bw=6e11,
                               peak_flops=2e13)
    exports = []
    for name in ("rec_q2", "simple_q1"):
        w = workloads.ALL_WORKLOADS[name](scale=SCALE)
        b = cost.plan_cost_breakdown(w.plan, w.catalog, prior)
        t = (b.flops / true.peak_flops
             + (b.hbm_bytes + b.param_bytes) / true.hbm_bw
             + b.n_ops * true.op_overhead_s)
        exports.append(feedback.SignatureExport(
            key=name, requests=20, dispatches=20, mean_occupancy=1.0,
            mean_dispatch_s=t, mean_wait_s=0.0, plan=w.plan,
            catalog=w.catalog))
    return exports


def test_calibrated_profile_changes_lowering_decision_without_aliasing():
    """Acceptance: feedback-calibrated profiles change a lowering decision
    in a fixed-seed test, and the PlanCache selects a different executable
    under a new key instead of aliasing the stale one."""
    w = workloads.rec_q2(scale=SCALE)
    cache = PlanCache()
    k0 = cache.key(w.plan, w.catalog)
    fn0 = cache.get_or_compile(w.plan, w.catalog)
    assert cache._cache.get(k0) is fn0

    fit = feedback.apply_calibration(cache, _true_device_exports(cache.profile))
    assert fit.n_samples == 2
    assert cache.profile_epoch == 1
    # per-op overhead rose by orders of magnitude: the marginal compaction
    # no longer pays, so the decision vector (and the key) change
    k1 = cache.key(w.plan, w.catalog)
    fn1 = cache.get_or_compile(w.plan, w.catalog)
    assert k1 != k0, "calibration did not change the lowering decision"
    assert fn1 is not fn0, "stale executable aliased after recalibration"
    # the old entry is still the old executable under the old key (LRU
    # retires it eventually); the new key maps to the new one
    assert cache._cache.get(k0) is fn0
    assert cache._cache.get(k1) is fn1
    # results agree: realizations only differ in predicted latency
    out0 = fn0(dict(w.catalog.tables)).canonical()
    out1 = fn1(dict(w.catalog.tables)).canonical()
    assert_tables_equal(out0, out1, "recalibrated")


def test_stale_submit_memo_key_is_refreshed_after_recalibration():
    """The serving tier memoizes keys at admission; a recalibrated profile
    must invalidate the memo (epoch check), not dispatch stale keys."""
    from repro.serving.server import QueryServer

    w = workloads.rec_q2(scale=SCALE)
    server = QueryServer(max_batch_size=1, max_wait_s=0.0)
    r0 = server.submit(w.plan, w.catalog)
    server.drain()
    feedback.apply_calibration(server.cache,
                               _true_device_exports(server.cache.profile))
    r1 = server.submit(w.plan, w.catalog)
    server.drain()
    assert r0.error is None and r1.error is None
    assert r1.key != r0.key, "submit memo served a stale pre-calibration key"


# ---------------------------------------------------------------------------
# vmapped-vs-sharded batch realization through the oracle
# ---------------------------------------------------------------------------

def test_choose_batch_realization_costed():
    jax_mesh = pytest.importorskip("jax.sharding")
    import jax
    from repro.core import mesh as mesh_util

    w = workloads.simple_q1(scale=0.3)
    if len(jax.devices()) > 1:
        mesh = mesh_util.data_mesh()
        ways = mesh_util.batch_ways(mesh)
        b = 2 * ways
        # default collective priors are small (non-zero, so collectives are
        # never free): sharding an eligible batch is still predicted to pay
        assert costed_lowering.choose_batch_realization(
            w.plan, w.catalog, b, mesh) == "sharded"
        # a profile whose per-shard collective overhead dwarfs the work
        # flips the choice to the single-device vmapped program
        slow = dataclasses.replace(cost.DeviceProfile.detect(),
                                   collective_overhead_s=10.0)
        assert costed_lowering.choose_batch_realization(
            w.plan, w.catalog, b, mesh, profile=slow) == "batched"
    # ineligible is always batched
    assert costed_lowering.choose_batch_realization(
        w.plan, w.catalog, 4, None) == "batched"
