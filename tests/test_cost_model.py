"""Cost-oracle properties: profile detection, compaction-placement and
pallas-vs-jnp monotonicity (property-style over the 12 workloads), batched
/sharded scaling, and calibration fitting."""
import dataclasses

import numpy as np
import pytest

from repro.core import cost, ir, stage_graph
from repro.core.lowering import lower
from repro.data import workloads
from repro.mlfuncs import builders
from repro.mlfuncs.registry import Registry


# ---------------------------------------------------------------------------
# DeviceProfile.detect
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


def _fake_backend(monkeypatch, backend, kind):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_FakeDevice(kind)])


def test_detect_maps_jax_backend(monkeypatch):
    for backend, kind, name, pallas in (
            ("tpu", "TPU v5 lite", "tpu-v5e", True),
            ("gpu", "NVIDIA A100-SXM4-40GB", "gpu-a100", False),
            ("cpu", "cpu", "cpu", False)):
        _fake_backend(monkeypatch, backend, kind)
        p = cost.DeviceProfile.detect()
        assert p.name == name and p.supports_pallas == pallas
    monkeypatch.undo()
    # detect() returns fresh copies: calibrating one must not leak into the
    # module priors
    p = cost.DeviceProfile.detect()
    p.op_overhead_s = 123.0
    assert cost.CPU_PROFILE.op_overhead_s != 123.0
    assert cost.DeviceProfile.detect().op_overhead_s != 123.0


def test_detect_v5e_kind_gets_v5e_published_peaks(monkeypatch):
    _fake_backend(monkeypatch, "tpu", "TPU v5 lite")
    p = cost.DeviceProfile.detect()
    assert (p.peak_flops, p.hbm_bw) == (197e12, 819e9)
    assert p is not cost.TPU_PRIORS["TPU v5 lite"]  # a copy, not the prior


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v5", "TPU v6 lite", ""])
def test_detect_unknown_tpu_kind_raises(monkeypatch, kind):
    _fake_backend(monkeypatch, "tpu", kind)
    with pytest.raises(ValueError, match="no cost prior for TPU device kind"):
        cost.DeviceProfile.detect()


def test_profile_signature_tracks_calibratable_fields():
    a = cost.DeviceProfile.detect()
    b = dataclasses.replace(a, op_overhead_s=a.op_overhead_s * 2)
    assert a.signature() != b.signature()
    assert a.signature() == dataclasses.replace(a).signature()


# ---------------------------------------------------------------------------
# compaction placement monotonicity (property over the 12 workloads)
# ---------------------------------------------------------------------------

def _selective_filters_over_full_inputs(plan, catalog):
    """Filters whose *own* selectivity is the source of the shrink: the
    input's sound live-row bound fills its capacity, while the output's
    sound bound compacts strictly below it."""
    out = []
    for n in ir.walk(plan.root):
        if not isinstance(n, ir.Filter):
            continue
        b_after = stage_graph.sound_rows_bound(n, plan.registry, catalog)
        b_before = stage_graph.sound_rows_bound(n.child, plan.registry,
                                                catalog)
        if b_after is None or b_before is None:
            continue
        cap = ir.infer(n, plan.registry, catalog).capacity
        if (b_before >= cap * 0.95
                and stage_graph.compact_capacity(b_after) < cap):
            out.append((n, b_after))
    return out


def test_compact_after_selective_filter_cheaper_than_before():
    """Compaction *after* a selective filter must cost less than before it.

    Capacities are position-dependent correctness bounds: before the filter
    the soundest compact cannot shrink below the input's live rows (here:
    the full capacity — pure overhead), while after the filter it shrinks
    to the surviving rows and every downstream pass gets cheaper. This is
    exactly why the stage graph glues inserted compacts *behind* their
    filter. Property-style over every eligible workload."""
    profile = cost.DeviceProfile.detect()
    checked = 0
    for name in sorted(workloads.ALL_WORKLOADS):
        w = workloads.ALL_WORKLOADS[name](scale=0.5)
        for f, bound_after in _selective_filters_over_full_inputs(w.plan,
                                                                  w.catalog):
            cap_in = ir.infer(f, w.plan.registry, w.catalog).capacity
            cap_after = stage_graph.compact_capacity(bound_after)
            after_root = ir.replace_node(
                w.plan.root, f, ir.Compact(f, capacity=cap_after))
            before_root = ir.replace_node(
                w.plan.root, f, dataclasses.replace(
                    f, child=ir.Compact(f.child, capacity=cap_in)))
            c_after = cost.plan_cost(
                ir.Plan(after_root, w.plan.registry, w.plan.phys),
                w.catalog, profile)
            c_before = cost.plan_cost(
                ir.Plan(before_root, w.plan.registry, w.plan.phys),
                w.catalog, profile)
            assert c_after < c_before, (name, cap_after, cap_in)
            checked += 1
    assert checked >= 3, "too few workloads with a selective filter"


def test_costed_lowering_places_compact_after_the_selective_filter():
    """The stage graph only ever glues an inserted compact *after* its
    filter, and the chosen plan is never analytically worse than tree
    order (the oracle's pick is consistent with the monotonicity above)."""
    from repro.core import physical as ph

    w = workloads.rec_q1(scale=0.5)
    pplan = lower(w.plan, w.catalog)

    def pipelines(node):
        if isinstance(node, ph.PPipeline):
            yield node
        for c in node.children():
            yield from pipelines(c)

    inserted = 0
    for p in pipelines(pplan.root):
        kinds = [type(s).__name__ for s in p.stages]
        for i, k in enumerate(kinds):
            if k == "CompactStage":
                assert i > 0 and kinds[i - 1] == "FilterStage"
                inserted += 1
    assert inserted >= 1, "expected an inserted compact on rec_q1"


# ---------------------------------------------------------------------------
# pallas-vs-jnp consistency (property over the 12 workloads)
# ---------------------------------------------------------------------------

def _r3_annotated_plans(w, rule_name):
    from repro.core.rules import ALL_RULES
    rule = ALL_RULES[rule_name]
    cfgs = rule.configs(w.plan, w.catalog)
    if not cfgs:
        return None
    return rule.apply(w.plan, w.catalog, cfgs[0])


def test_pallas_costs_less_than_jnp_exactly_when_model_says_so():
    """For every workload where an R3 rule applies: the pallas realization
    of the annotated node costs less than jnp exactly when the analytic
    model's bandwidth term is binding (pallas reads through vmem_bw >
    hbm_bw; the compute term is backend-independent)."""
    profile = cost.TPU_PROFILE  # pallas-capable (analytic only, no exec)
    checked = 0
    for name in sorted(workloads.ALL_WORKLOADS):
        w = workloads.ALL_WORKLOADS[name](scale=0.5)
        plan = (_r3_annotated_plans(w, "R3-1")
                or _r3_annotated_plans(w, "R3-2"))
        if plan is None:
            continue
        uid, cfg = next(iter(plan.phys.items()))
        p_jnp = plan.with_phys(uid, dataclasses.replace(cfg, backend="jnp"))
        p_pal = plan.with_phys(uid, dataclasses.replace(cfg, backend="pallas"))
        c_jnp = cost.plan_cost(p_jnp, w.catalog, profile)
        c_pal = cost.plan_cost(p_pal, w.catalog, profile)
        # find the annotated node's operator and ask the model which term
        # binds (the rule made the plan's one matmul or forest node)
        ocs = [oc for oc in cost.phys_op_costs(
                   lower(p_jnp, w.catalog, costed=False), w.catalog, profile)
               if oc.label in ("matmul", "forest")]
        assert len(ocs) == 1, name
        oc = ocs[0]
        bw_bound = ((oc.data_bytes + oc.param_bytes) / profile.hbm_bw
                    > oc.flops / profile.peak_flops)
        if bw_bound:
            assert c_pal < c_jnp, name
        else:
            assert c_pal == pytest.approx(c_jnp, rel=1e-12), name
        checked += 1
    assert checked >= 3


# ---------------------------------------------------------------------------
# batched / sharded scaling
# ---------------------------------------------------------------------------

def test_batched_cost_scales_with_occupancy_and_shards():
    w = workloads.rec_q2(scale=0.3)
    prof = cost.CPU_PROFILE
    c1 = cost.batched_plan_cost(w.plan, w.catalog, 1, prof)
    c8 = cost.batched_plan_cost(w.plan, w.catalog, 8, prof)
    assert c8 > c1  # more queries, more work
    c8s = cost.batched_plan_cost(w.plan, w.catalog, 8, prof, ways=4)
    assert c8s < c8  # four shards each run the 2-query slice
    slow = dataclasses.replace(prof, collective_overhead_s=10.0)
    assert (cost.batched_plan_cost(w.plan, w.catalog, 8, slow, ways=4)
            > cost.batched_plan_cost(w.plan, w.catalog, 8, slow))


# ---------------------------------------------------------------------------
# calibration fit
# ---------------------------------------------------------------------------

def _samples(profile, names=("rec_q2", "simple_q1", "retail_q1"), scale=0.5,
             true=None):
    out = []
    for name in names:
        w = workloads.ALL_WORKLOADS[name](scale=scale)
        b = cost.plan_cost_breakdown(w.plan, w.catalog, profile)
        ref = true or profile
        t = (b.flops / ref.peak_flops
             + (b.hbm_bytes + b.param_bytes) / ref.hbm_bw
             + b.n_ops * ref.op_overhead_s)
        out.append((b, t, 1.0))
    return out


def test_fit_profile_recovers_prior_on_consistent_data():
    prior = cost.CPU_PROFILE
    fit = cost.fit_profile(_samples(prior), prior)
    assert fit.mape_after < 1e-6
    assert fit.profile.peak_flops == pytest.approx(prior.peak_flops, rel=0.05)
    assert fit.profile.op_overhead_s == pytest.approx(prior.op_overhead_s,
                                                      rel=0.05)


def test_fit_profile_moves_toward_true_device():
    prior = cost.CPU_PROFILE
    true = dataclasses.replace(prior, op_overhead_s=5e-4, hbm_bw=6e11,
                               peak_flops=2e13)
    fit = cost.fit_profile(_samples(prior, true=true), prior)
    assert fit.mape_after < fit.mape_before
    # direction (not exactness): every coefficient moved toward the truth
    assert fit.profile.op_overhead_s > prior.op_overhead_s * 10
    assert fit.profile.hbm_bw > prior.hbm_bw
    assert fit.profile.peak_flops > prior.peak_flops
    assert fit.profile.name.endswith("+cal")


def test_fit_profile_is_bounded_against_pathological_data():
    prior = cost.CPU_PROFILE
    b = cost.CostBreakdown(flops=1.0, hbm_bytes=1.0, param_bytes=0.0,
                           vmem_bytes=0.0, n_ops=1, seconds=1.0)
    fit = cost.fit_profile([(b, 1e6, 1.0)], prior)  # absurd measurement
    p = fit.profile
    assert prior.op_overhead_s / 100 <= p.op_overhead_s <= prior.op_overhead_s * 100
    assert prior.hbm_bw / 100 <= p.hbm_bw <= prior.hbm_bw * 100
    assert cost.fit_profile([], prior).n_samples == 0


def test_breakdown_scaled_rides_the_batch_axis():
    w = workloads.simple_q1(scale=0.3)
    b = cost.plan_cost_breakdown(w.plan, w.catalog, cost.CPU_PROFILE)
    s = b.scaled(8.0)
    assert s.flops == pytest.approx(8 * b.flops)
    assert s.hbm_bytes == pytest.approx(8 * b.hbm_bytes)
    assert s.param_bytes == b.param_bytes  # weights stream once
    assert s.n_ops == b.n_ops


# ---------------------------------------------------------------------------
# the over-budget penalty
# ---------------------------------------------------------------------------

def test_where_no_plan_fits_the_smaller_overshoot_wins():
    """Two plans over the budget: the one with the smaller peak costs
    less, though it is the slower, so that a search keeps a gradient
    toward a plan that fits; each costs more than either would if it fit."""
    from repro.relational.table import Table
    reg = Registry()
    reg.register(builders.ffnn("wide", [64, 4096, 1]))
    reg.register(builders.ffnn("deep", [64, 512, 512, 512, 512, 1]))
    catalog = ir.Catalog()
    catalog.add("t", Table.from_columns(
        {"x": np.zeros((8192, 64), np.float32)}))

    def plan(fn):
        return ir.Plan(ir.Project(ir.Scan("t"), outputs=(
            ("y", ir.Call(fn, (ir.Col("x"),))),), keep=()), reg)

    plans = {fn: plan(fn) for fn in ("wide", "deep")}
    profile = cost.DeviceProfile.detect()

    def each(f):
        return {fn: f(p) for fn, p in plans.items()}

    peak = each(lambda p: cost.plan_peak_memory(p, catalog, profile))
    t = each(lambda p: cost.plan_cost(p, catalog, profile,
                                      memory_budget=np.inf))
    assert peak["deep"] < peak["wide"] and t["deep"] > t["wide"]
    budget = peak["deep"] / 2
    over = each(lambda p: cost.plan_cost(p, catalog, profile,
                                         memory_budget=budget))
    assert over["deep"] < over["wide"]
    assert min(over.values()) > max(t.values())
