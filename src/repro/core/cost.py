"""Analytic cost model — the single cost oracle of the stack.

Every component that needs a notion of "cheap" routes through ``plan_cost``:
the MCTS reward oracle (``planner.analytic_cost_fn`` / ``mcts.VanillaMCTS``),
costed lowering (``core.costed_lowering`` scores physical candidates), the
serving tier's batch-realization choice (``batched_plan_cost``), and the
online feedback calibration (``fit_profile`` refits a ``DeviceProfile``
against measured dispatch latencies). Every entry point accepts both the
logical ``ir.Plan`` and the physical ``physical.PhysicalPlan``: a logical plan
is costed on its tree-order lowering (``lowering.lower(costed=False)``), so
there is one walk for time (``phys_op_costs``) and one for memory
(``phys_peak_memory``).

Costs each operator by FLOPs + bytes moved against a device profile, using
capacity (static shape) rather than live-row counts — on TPU the *capacity*
drives cost, which is exactly why compaction after selective filters matters
(DESIGN.md Sec. 2). The learned latency predictor (core.embedding) plays the
paper's Query2Vec role and is trained against measured wall-clock of
compiled plans; it is deliberately a separate estimator.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import tracing
from repro.core import ir
from repro.mlfuncs.registry import Registry

@dataclasses.dataclass
class DeviceProfile:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12      # bf16 FLOP/s
    hbm_bw: float = 819e9           # bytes/s
    vmem_bw: float = 4.0e12         # effective on-chip bandwidth for fused ops
    elem_bytes: int = 4
    # fixed overhead per relational operator (dispatch/fusion boundary)
    op_overhead_s: float = 2e-6
    # per-shard fan-in/out overhead of a multi-device (sharded) dispatch and
    # per-shard launch cost of one in-plan collective (allgather/psum).
    # Every backend prior is non-zero: a 0.0 default would price all
    # collectives as free and silently bias every sharded-vs-local decision
    # toward sharding; serving/feedback.py calibrates it online alongside
    # peak_flops/hbm_bw/op_overhead_s when sharded traffic exists.
    collective_overhead_s: float = 1e-6
    # per-device working-set budget in bytes (None = unlimited): costed
    # lowering hard-rejects candidates whose phys_peak_memory exceeds it,
    # and plan_cost prices a plan over it as one that does not run
    # (``OVER_BUDGET``). ``detect`` sets a TPU's to the device's allocator
    # limit; the serving tier may install another
    # (QueryServer(memory_budget=...)).
    memory_budget: Optional[float] = None
    # whether the pallas kernel realizations are executable on this device
    supports_pallas: bool = True

    def signature(self) -> str:
        """Calibratable-field token: anything the feedback loop can move.
        Two profiles with equal signatures make identical lowering
        decisions. (PlanCache invalidates its decision memos via
        ``profile_epoch``, bumped by ``recalibrate()`` — mutating a
        profile's fields in place does NOT re-derive decisions.)"""
        mb = "-" if self.memory_budget is None else f"{self.memory_budget:.4e}"
        return (f"{self.name}:pf={self.peak_flops:.4e},bw={self.hbm_bw:.4e},"
                f"vb={self.vmem_bw:.4e},ov={self.op_overhead_s:.4e},"
                f"co={self.collective_overhead_s:.4e},mb={mb}")

    @classmethod
    def detect(cls) -> "DeviceProfile":
        """A fresh profile for the host's JAX backend.

        A TPU's prior is chosen by the ``device_kind`` JAX reports
        (``TPU_PRIORS``); a TPU kind without a prior raises instead of
        borrowing another chip's peaks, because every pallas-vs-jnp,
        compaction and PartSpec decision is costed against them. A TPU's
        memory budget is the device's ``bytes_limit``, so that a plan whose
        analytic peak does not fit loses to one that does.
        Returns a *copy* (profiles are mutable calibration targets; the
        module singletons below are priors, never calibrated in place).
        """
        import jax
        backend = jax.default_backend()
        if backend == "tpu":
            kind = jax.devices()[0].device_kind
            if kind not in TPU_PRIORS:
                raise ValueError(
                    f"no cost prior for TPU device kind {kind!r}; known: "
                    f"{sorted(TPU_PRIORS)} (add its published peaks to "
                    f"repro.core.cost.TPU_PRIORS)")
            prior = TPU_PRIORS[kind]
            # the budget is the device's own: what its allocator may hand out
            stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
            limit = (stats or {}).get("bytes_limit")
            if limit:
                return dataclasses.replace(prior, memory_budget=float(limit))
        elif backend in ("gpu", "cuda", "rocm"):
            prior = GPU_PROFILE
        else:
            prior = CPU_PROFILE
        return dataclasses.replace(prior)


# collective priors: per-shard launch latency of one ICI/NVLink collective
# on real accelerators; the "devices" of a forced CPU host mesh share one
# address space, so a collective there is a plain memcpy whose *volume*
# already rides data_bytes — only a tiny per-launch latency remains
#
# TPU v5e peaks: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM bandwidth, 16 GB HBM per chip. vmem_bw and the overheads are
# guesses, not measurements.
TPU_PROFILE = DeviceProfile(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                            collective_overhead_s=1e-6)

# TPU priors keyed by ``jax.Device.device_kind`` (what the chip reports)
TPU_PRIORS = {"TPU v5 lite": TPU_PROFILE}

GPU_PROFILE = DeviceProfile(name="gpu-a100", peak_flops=312e12,
                            hbm_bw=1.55e12, vmem_bw=5.0e12,
                            op_overhead_s=3e-6, collective_overhead_s=2e-6,
                            supports_pallas=False)

CPU_PROFILE = DeviceProfile(name="cpu", peak_flops=2e11, hbm_bw=3e10,
                            vmem_bw=2e11, op_overhead_s=5e-6,
                            collective_overhead_s=2e-7,
                            supports_pallas=False)

_DETECTED: Optional[DeviceProfile] = None


def default_profile() -> DeviceProfile:
    """Process-wide detected profile (lazy, computed once). Default for
    every ``plan_cost`` entry that is not handed an explicit profile."""
    global _DETECTED
    if _DETECTED is None:
        _DETECTED = DeviceProfile.detect()
    return _DETECTED


# ---------------------------------------------------------------------------
# per-operator cost kernels
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpCost:
    """One physical operator's resource footprint, device-independent.

    ``data_bytes`` scale with the data/batch axis (a B-query vmapped
    dispatch moves B x data_bytes); ``param_bytes`` are weight traffic,
    streamed once per dispatch and replicated across shards. ``n_ops``
    counts dispatch/fusion-boundary overhead units (``op_overhead_s``);
    ``n_coll`` counts per-shard collective launches
    (``collective_overhead_s`` — a ``ways``-way allgather/psum pays
    ``ways`` of them, its exchange volume rides ``data_bytes``).
    """
    label: str
    flops: float = 0.0
    data_bytes: float = 0.0
    param_bytes: float = 0.0
    bw: str = "hbm"              # 'hbm' | 'vmem' (pallas-fused operators)
    n_ops: int = 1
    n_coll: int = 0


def op_time(oc: OpCost, profile: DeviceProfile, data_scale: float = 1.0) -> float:
    """Roofline time of one operator: max(compute, traffic) + overhead."""
    bw = profile.vmem_bw if oc.bw == "vmem" else profile.hbm_bw
    return (max(oc.flops * data_scale / profile.peak_flops,
                (oc.data_bytes * data_scale + oc.param_bytes) / bw)
            + oc.n_ops * profile.op_overhead_s
            + oc.n_coll * profile.collective_overhead_s)


def _row_bytes(schema: Dict[str, int], profile: DeviceProfile) -> float:
    return sum(max(d, 1) for d in schema.values()) * profile.elem_bytes


def _filter_cost(pred_flops: float, schema, capacity, profile) -> OpCost:
    return OpCost("filter", flops=pred_flops * capacity,
                  data_bytes=_row_bytes(schema, profile) * capacity)


def _compact_cost(schema, cap_in, cap_out, profile) -> OpCost:
    return OpCost("compact", flops=cap_in * 8.0,  # sort + gather
                  data_bytes=_row_bytes(schema, profile) * (cap_in + cap_out))


def _project_cost(expr_flops: float, in_schema, out_schema, param_bytes,
                  capacity, profile) -> OpCost:
    by = (_row_bytes(in_schema, profile)
          + _row_bytes(out_schema, profile)) * capacity
    return OpCost("project", flops=expr_flops * capacity, data_bytes=by,
                  param_bytes=param_bytes)


def _join_cost(l_schema, l_cap, r_schema, r_cap, out_schema, out_cap,
               profile) -> OpCost:
    fl = (l_cap + r_cap) * 32.0  # sort/searchsorted
    by = (_row_bytes(l_schema, profile) * l_cap
          + _row_bytes(r_schema, profile) * r_cap
          + _row_bytes(out_schema, profile) * out_cap)
    return OpCost("join", flops=fl, data_bytes=by)


def _crossjoin_cost(out_schema, out_cap, profile) -> OpCost:
    return OpCost("crossjoin", flops=out_cap * 2.0,
                  data_bytes=2.0 * _row_bytes(out_schema, profile) * out_cap)


def _aggregate_cost(schema, capacity, n_aggs, profile) -> OpCost:
    return OpCost("aggregate", flops=capacity * (16.0 + 2.0 * n_aggs),
                  data_bytes=_row_bytes(schema, profile) * capacity)


def _matmul_cost(fn, x_dim, capacity, cfg: ir.PhysConfig, profile) -> OpCost:
    fl = fn.flops_per_row([x_dim]) * capacity
    pb = fn.param_bytes()
    xby = max(x_dim, 1) * profile.elem_bytes * capacity
    extra = 0
    if cfg.mode == "relational":
        # streamed tile scan: x re-read per tile + per-tile op overhead
        xby *= cfg.n_tiles
        extra = cfg.n_tiles
    return OpCost("matmul", flops=fl, data_bytes=2 * xby, param_bytes=pb,
                  bw="vmem" if cfg.backend == "pallas" else "hbm",
                  n_ops=1 + extra)


def _repartition_cost(node, schema, in_cap, profile) -> OpCost:
    """Partition-boundary cost: local copies for slice/bucket, exchange
    volume + per-shard collective launches for allgather/combine."""
    rb = _row_bytes(schema, profile)
    if node.op == "slice":
        return OpCost("repart_slice", data_bytes=2.0 * rb * node.out_capacity)
    if node.op == "allgather":
        # each device receives and writes the full reassembled table
        return OpCost("repart_allgather",
                      data_bytes=2.0 * rb * node.out_capacity,
                      n_coll=node.ways)
    if node.op == "bucket":
        # hash + compare on the key column, mask write
        return OpCost("repart_bucket", flops=4.0 * in_cap,
                      data_bytes=3.0 * profile.elem_bytes * in_cap)
    if node.op == "combine":
        # zero-and-psum of every column: full-table exchange per device
        return OpCost("repart_combine",
                      flops=float(max(len(schema), 1)) * in_cap,
                      data_bytes=2.0 * rb * node.out_capacity,
                      n_coll=node.ways)
    raise ValueError(f"unknown repartition op {node.op!r}")


def _forest_cost(fn, x_dim, capacity, cfg: ir.PhysConfig, profile) -> OpCost:
    fl = fn.flops_per_row([x_dim]) * capacity
    pb = fn.param_bytes()
    xby = max(x_dim, 1) * profile.elem_bytes * capacity
    if cfg.mode == "relational":
        p = fn.graph.nodes[0].atom.params
        xby *= p["feat"].shape[0]  # x re-read once per streamed tree
    return OpCost("forest", flops=fl, data_bytes=xby, param_bytes=pb,
                  bw="vmem" if cfg.backend == "pallas" else "hbm")


def _calls(e: ir.Expr):
    if isinstance(e, ir.Call):
        yield e
    for c in e.children():
        yield from _calls(c)


def _param_bytes(exprs, registry: Registry) -> float:
    """Weight bytes of every ML call in ``exprs`` (streamed once per call)."""
    return sum(registry.get(c.fn).param_bytes()
               for e in exprs for c in _calls(e))


# ---------------------------------------------------------------------------
# the plan walks: time and memory
# ---------------------------------------------------------------------------

def _stage_info(stage, schema: Dict[str, int], capacity: int,
                registry: Registry) -> Tuple[Dict[str, int], int]:
    """Schema/capacity after one pipeline stage (exact, statically known)."""
    from repro.core import physical as ph
    if isinstance(stage, ph.FilterStage):
        return schema, capacity
    if isinstance(stage, ph.CompactStage):
        return schema, stage.capacity
    if isinstance(stage, ph.ProjectStage):
        out = (dict(schema) if stage.keep is None
               else {k: schema[k] for k in stage.keep})
        for name, e in stage.outputs:
            out[name] = ir.expr_dim(e, schema, registry)
        return out, capacity
    raise TypeError(type(stage))


def _derive_info(node, registry: Registry, catalog: ir.Catalog,
                 child_infos) -> Tuple[Dict[str, int], int]:
    """(schema, capacity) of a physical node's output from its children's
    already-computed infos — single level, so walks that visit each node
    once stay linear in plan size."""
    from repro.core import physical as ph
    if isinstance(node, ph.PScan):
        st = catalog.stats[node.table]
        return {c: s.dim for c, s in st.columns.items()}, st.capacity
    if isinstance(node, ph.PPipeline):
        schema, cap = child_infos[0]
        for stage in node.stages:
            schema, cap = _stage_info(stage, schema, cap, registry)
        return schema, cap
    if isinstance(node, ph.PJoin):
        (ls, lc), (rs, _) = child_infos
        schema = dict(ls)
        for c, d in rs.items():
            out = node.rprefix + c
            if out == node.left_key and c == node.right_key:
                continue
            schema[out] = d
        return schema, lc
    if isinstance(node, ph.PCrossJoin):
        (ls, lc), (rs, rc) = child_infos
        schema = {node.aprefix + c: d for c, d in ls.items()}
        schema.update({node.bprefix + c: d for c, d in rs.items()})
        return schema, lc * rc
    if isinstance(node, ph.PAggregate):
        cs, _ = child_infos[0]
        schema = {node.key: 0}
        for out, (kind, in_col) in node.aggs:
            schema[out] = 0 if kind == "count" else cs.get(in_col, 0)
        return schema, node.num_groups
    if isinstance(node, ph.PBlockedMatmul):
        cs, cc = child_infos[0]
        schema = dict(cs) if node.keep is None else {k: cs[k] for k in node.keep}
        schema[node.out_col] = registry.get(node.fn).out_dim([cs[node.x_col]])
        return schema, cc
    if isinstance(node, ph.PForestRelational):
        cs, cc = child_infos[0]
        schema = dict(cs) if node.keep is None else {k: cs[k] for k in node.keep}
        schema[node.out_col] = 0
        return schema, cc
    if isinstance(node, ph.PRepartition):
        cs, cc = child_infos[0]
        if node.op in ("slice", "allgather"):
            # the walk downstream of a slice sees the per-device block
            # capacity, which is what makes the physical walk price (and
            # phys_peak_memory bound) *per-device* work on partitioned plans
            return cs, node.out_capacity
        return cs, cc  # bucket/combine: capacity unchanged
    raise TypeError(type(node))


def phys_op_costs(pplan, catalog: ir.Catalog,
                  profile: DeviceProfile) -> List[OpCost]:
    """Per-operator OpCosts of a physical plan."""
    from repro.core import physical as ph
    registry = pplan.registry
    out: List[OpCost] = []

    def visit(node) -> Tuple[Dict[str, int], int]:
        child_infos = tuple(visit(c) for c in node.children())
        if isinstance(node, ph.PPipeline):
            schema, cap = child_infos[0]
            for stage in node.stages:
                nxt = _stage_info(stage, schema, cap, registry)
                if isinstance(stage, ph.FilterStage):
                    out.append(_filter_cost(
                        ir.expr_flops(stage.pred, schema, registry),
                        schema, cap, profile))
                elif isinstance(stage, ph.CompactStage):
                    out.append(_compact_cost(schema, cap, stage.capacity,
                                             profile))
                elif isinstance(stage, ph.ProjectStage):
                    exprs = _stage_exprs(stage)
                    fl = sum(ir.expr_flops(e, schema, registry) for e in exprs)
                    out.append(_project_cost(fl, schema, nxt[0],
                                             _param_bytes(exprs, registry),
                                             cap, profile))
                schema, cap = nxt
            return schema, cap
        info = _derive_info(node, registry, catalog, child_infos)
        if isinstance(node, ph.PJoin):
            (ls, lc), (rs, rc) = child_infos
            out.append(_join_cost(ls, lc, rs, rc, info[0], info[1], profile))
        elif isinstance(node, ph.PCrossJoin):
            out.append(_crossjoin_cost(info[0], info[1], profile))
        elif isinstance(node, ph.PAggregate):
            cs, cc = child_infos[0]
            out.append(_aggregate_cost(cs, cc, len(node.aggs), profile))
        elif isinstance(node, ph.PBlockedMatmul):
            cs, cc = child_infos[0]
            cfg = ir.PhysConfig(mode=node.mode, backend=node.backend,
                                n_tiles=node.n_tiles)
            out.append(_matmul_cost(registry.get(node.fn), cs[node.x_col],
                                    cc, cfg, profile))
        elif isinstance(node, ph.PForestRelational):
            cs, cc = child_infos[0]
            cfg = ir.PhysConfig(mode=node.mode, backend=node.backend)
            out.append(_forest_cost(registry.get(node.fn), cs[node.x_col],
                                    cc, cfg, profile))
        elif isinstance(node, ph.PRepartition):
            cs, cc = child_infos[0]
            out.append(_repartition_cost(node, cs, cc, profile))
        elif not isinstance(node, ph.PScan):
            raise TypeError(type(node))
        return info

    visit(pplan.root)
    return out


def _dense_bytes(exprs, registry: Registry, cap,
                 profile: DeviceProfile) -> float:
    """Working set of the dense layers that ``exprs``' calls run over
    ``cap`` rows: the widest layer's input and output. A layer of width 1
    holds neither: XLA reduces it into the ops that produce its input."""
    widest = 0
    for e in exprs:
        for c in _calls(e):
            g = registry.get(c.fn).graph
            for n in (g.nodes if g is not None else ()):
                if n.atom.kind in ("matmul", "fused_dense"):
                    w = n.atom.params["w"]
                    if w.shape[1] > 1:
                        widest = max(widest, w.shape[0] + w.shape[1])
    return float(widest) * cap * profile.elem_bytes


def _stage_exprs(stage) -> tuple:
    """The expressions a Filter or Project stage evaluates."""
    from repro.core import physical as ph
    if isinstance(stage, ph.FilterStage):
        return (stage.pred,)
    if isinstance(stage, ph.ProjectStage):
        return tuple(e for _, e in stage.outputs)
    return ()


def _view_bytes(schema, cap, view, profile) -> float:
    """Bytes of a relation whose ``view`` columns (a cross join's, not
    materialized) live in its sides' ``view`` bytes instead."""
    if view is None:
        return _row_bytes(schema, profile) * cap
    cols, side = view
    return _row_bytes({c: d for c, d in schema.items() if c not in cols},
                      profile) * cap + side


def phys_peak_memory(pplan, catalog: ir.Catalog,
                     profile: DeviceProfile) -> float:
    """Peak working set of a physical plan (max across operators).

    A cross join's output is a view of its sides (``ops.cross_join``
    broadcasts them; XLA fuses the broadcast into the row-local stages
    that read it): a stage of the pipeline above it holds the columns it
    computes at the product's capacity, and the sides. A Compact gathers
    the view, and an operator other than a pipeline stage, or the plan's
    output, materializes it. A Filter or Project stage also holds its
    widest dense layer's input and output (``_dense_bytes``)."""
    from repro.core import physical as ph
    registry = pplan.registry
    peak = 0.0

    def base(schema, cap) -> float:
        return _row_bytes(schema, profile) * cap

    def visit(node):
        nonlocal peak
        kids = tuple(visit(c) for c in node.children())
        for schema, cap, view in kids:
            if view is not None and not isinstance(node, ph.PPipeline):
                peak = max(peak, base(schema, cap))  # materialized
        child_infos = tuple(k[:2] for k in kids)
        if isinstance(node, ph.PScan):
            schema, cap = _derive_info(node, registry, catalog, child_infos)
            peak = max(peak, base(schema, cap))
            return schema, cap, None
        if isinstance(node, ph.PCrossJoin):
            schema, cap = _derive_info(node, registry, catalog, child_infos)
            side = sum(base(s, c) for s, c in child_infos)
            peak = max(peak, side)
            return schema, cap, (frozenset(schema), side)
        if isinstance(node, ph.PPipeline):
            schema, cap, view = kids[0]
            for stage in node.stages:
                act = _dense_bytes(_stage_exprs(stage), registry, cap, profile)
                schema, cap = _stage_info(stage, schema, cap, registry)
                if view is not None:
                    if isinstance(stage, ph.CompactStage):
                        view = None
                    elif isinstance(stage, ph.ProjectStage):
                        made = {n for n, _ in stage.outputs}
                        view = (view[0] & set(schema) - made, view[1])
                m = _view_bytes(schema, cap, view, profile) + act
                if isinstance(stage, ph.ProjectStage):
                    m += _param_bytes(_stage_exprs(stage), registry)
                peak = max(peak, m)
            return schema, cap, view
        schema, cap = _derive_info(node, registry, catalog, child_infos)
        m = base(schema, cap)
        if isinstance(node, ph.PBlockedMatmul):
            fn = registry.get(node.fn)
            # streamed: only one weight tile resident at a time
            m += fn.param_bytes() / max(node.n_tiles, 1)
        elif isinstance(node, ph.PForestRelational):
            fn = registry.get(node.fn)
            p = fn.graph.nodes[0].atom.params
            m += fn.param_bytes() / max(int(p["feat"].shape[0]), 1)
        elif isinstance(node, ph.PRepartition) and node.op == "allgather":
            # the gather target holds the padded concatenation of every
            # device's block (in_capacity = per-device block) briefly
            m = base(schema, node.in_capacity * node.ways)
        peak = max(peak, m)
        return schema, cap, None

    schema, cap, view = visit(pplan.root)
    if view is not None:  # the result is materialized
        peak = max(peak, base(schema, cap))
    return peak


def _physical(plan, catalog: ir.Catalog):
    """``plan`` as the physical plan the walks cost: a logical plan's
    tree-order lowering, a physical plan as it is."""
    from repro.core import physical as ph
    if isinstance(plan, ph.PhysicalPlan):
        return plan
    from repro.core.lowering import lower
    return lower(plan, catalog, costed=False)


def plan_peak_memory(plan, catalog: ir.Catalog,
                     profile: DeviceProfile | None = None) -> float:
    return phys_peak_memory(_physical(plan, catalog), catalog,
                            profile or default_profile())


# ---------------------------------------------------------------------------
# the single entry point
# ---------------------------------------------------------------------------

OVER_BUDGET = 1e6


def plan_cost(plan, catalog: ir.Catalog,
              profile: DeviceProfile | None = None,
              memory_budget: float | None = None) -> float:
    """Analytic plan latency — logical ``ir.Plan`` or physical
    ``PhysicalPlan`` alike; a plan whose working set exceeds the memory
    budget would not run (the paper's OOM failures): it costs
    ``OVER_BUDGET`` times its time times its peak over the budget, so that
    it loses to any plan that fits, and among those that do not the search
    is still drawn toward a smaller overshoot.
    ``memory_budget`` defaults to the profile's own per-device budget; a
    non-finite budget is explicitly unlimited (callers that already
    checked the peak themselves — costed lowering's hard gate — pass
    ``inf`` to skip the redundant peak walk)."""
    profile = profile or default_profile()
    if memory_budget is None:
        memory_budget = profile.memory_budget
    pplan = _physical(plan, catalog)
    t = sum(op_time(oc, profile)
            for oc in phys_op_costs(pplan, catalog, profile))
    if memory_budget is not None and np.isfinite(memory_budget):
        peak = phys_peak_memory(pplan, catalog, profile)
        if peak > memory_budget:
            t *= OVER_BUDGET * peak / memory_budget
            tracing.count("cost.demoted")
    return t


@dataclasses.dataclass
class CostBreakdown:
    """Profile-independent resource totals of one plan (plus the seconds the
    given profile predicts) — the calibration features of ``fit_profile``.
    ``hbm_bytes`` are per-query data traffic (they scale with batch
    occupancy); ``param_bytes`` stream once per dispatch. ``n_coll``
    counts per-shard collective launches (in-plan repartition boundaries
    and/or the sharded dispatch's fan-in/out) — the calibration feature of
    ``collective_overhead_s``."""
    flops: float
    hbm_bytes: float
    param_bytes: float
    vmem_bytes: float
    n_ops: int
    seconds: float
    n_coll: float = 0.0

    def scaled(self, occupancy: float) -> "CostBreakdown":
        """The breakdown of one ``occupancy``-query micro-batched dispatch:
        data traffic and FLOPs scale, weights and op count do not."""
        return dataclasses.replace(self, flops=self.flops * occupancy,
                                   hbm_bytes=self.hbm_bytes * occupancy,
                                   vmem_bytes=self.vmem_bytes * occupancy)


def plan_cost_breakdown(plan, catalog: ir.Catalog,
                        profile: DeviceProfile | None = None) -> CostBreakdown:
    profile = profile or default_profile()
    ocs = phys_op_costs(_physical(plan, catalog), catalog, profile)
    return CostBreakdown(
        flops=sum(oc.flops for oc in ocs),
        hbm_bytes=sum(oc.data_bytes for oc in ocs if oc.bw == "hbm"),
        param_bytes=sum(oc.param_bytes for oc in ocs if oc.bw == "hbm"),
        vmem_bytes=sum(oc.data_bytes + oc.param_bytes for oc in ocs
                       if oc.bw == "vmem"),
        n_ops=sum(oc.n_ops for oc in ocs),
        seconds=sum(op_time(oc, profile) for oc in ocs),
        n_coll=float(sum(oc.n_coll for oc in ocs)))


def batched_plan_cost(plan, catalog: ir.Catalog, batch_size: int,
                      profile: DeviceProfile | None = None,
                      ways: int = 1) -> float:
    """Predicted latency of one micro-batched dispatch of ``batch_size``
    same-signature queries: data traffic and FLOPs scale with the per-shard
    slice (``batch_size / ways``), weights are replicated (streamed once per
    shard), and a ``ways``-way sharded dispatch pays the profile's collective
    overhead per shard. ``ways=1`` is the vmapped single-device realization;
    the serving tier's vmapped-vs-sharded choice compares the two
    (``costed_lowering.choose_batch_realization``)."""
    profile = profile or default_profile()
    ocs = phys_op_costs(_physical(plan, catalog), catalog, profile)
    scale = batch_size / max(ways, 1)
    t = sum(op_time(oc, profile, data_scale=scale) for oc in ocs)
    if ways > 1:
        t += ways * profile.collective_overhead_s
    return t


# ---------------------------------------------------------------------------
# online calibration: measured latencies -> refitted profile
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CalibrationFit:
    profile: DeviceProfile
    n_samples: int
    mape_before: float
    mape_after: float


def _mape(pred: np.ndarray, actual: np.ndarray) -> float:
    actual = np.maximum(actual, 1e-12)
    return float(np.mean(np.abs(pred - actual) / actual))


def fit_profile(samples: Sequence[Tuple[CostBreakdown, float, float]],
                prior: DeviceProfile, l2: float = 0.1,
                max_shift: float = 100.0) -> CalibrationFit:
    """Least-squares refit of (peak_flops, hbm_bw, op_overhead_s,
    collective_overhead_s) from measured latencies.

    ``samples`` are ``(breakdown, measured_seconds, weight)`` triples; the
    linearized prediction ``flops/peak + bytes/bw + n_ops*overhead +
    n_coll*coll_overhead`` is fit in the coefficient space ``x = (1/peak,
    1/bw, overhead, coll_overhead)``. The loss is the weighted *relative*
    squared error (a 200us dispatch mispredicted 2x matters as much as a
    200ms one) plus a log-space ridge toward the prior — multiplicative
    shifts are what calibration corrects, so the penalty is symmetric in
    them, and under-determined directions (serving traffic rarely spans
    enough signatures to identify every coefficient; purely single-device
    traffic has an all-zero ``n_coll`` column) stay at the prior.
    Coefficients live in ``[prior/max_shift, prior*max_shift]`` so a
    pathological batch of measurements cannot turn the oracle nonsensical;
    a coefficient whose prior is zero is pinned (the log-space ridge has no
    anchor there). Solved by deterministic per-coordinate search over a
    refined log grid (4 coefficients; no solver dependency).
    """
    if not samples:
        return CalibrationFit(dataclasses.replace(prior), 0, 0.0, 0.0)
    A = np.array([[b.flops, b.hbm_bytes + b.param_bytes, float(b.n_ops),
                   float(b.n_coll)]
                  for b, _, _ in samples], dtype=np.float64)
    t = np.array([max(m, 1e-9) for _, m, _ in samples], dtype=np.float64)
    w = np.array([max(wt, 1e-12) for _, _, wt in samples], dtype=np.float64)
    x0 = np.array([1.0 / prior.peak_flops, 1.0 / prior.hbm_bw,
                   prior.op_overhead_s, prior.collective_overhead_s],
                  dtype=np.float64)
    active = [k for k in range(4) if x0[k] > 0]
    pred_before = A @ x0
    lo, hi = x0 / max_shift, x0 * max_shift
    w_total = float(np.sum(w))
    log_shift = np.log(max_shift)

    def objective(x: np.ndarray) -> float:
        rel = (A @ x - t) / t
        ridge = float(sum((np.log(x[k] / x0[k]) / log_shift) ** 2
                          for k in active))
        return float(np.sum(w * rel ** 2)) + l2 * w_total * ridge

    x = x0.copy()
    for _ in range(24):
        x_prev = x.copy()
        for k in active:
            span_lo, span_hi = np.log(lo[k]), np.log(hi[k])
            for _refine in range(3):
                grid = np.exp(np.linspace(span_lo, span_hi, 33))
                scores = []
                for g in grid:
                    xk = x.copy()
                    xk[k] = g
                    scores.append(objective(xk))
                bi = int(np.argmin(scores))
                x[k] = grid[bi]
                span_lo = np.log(grid[max(bi - 1, 0)])
                span_hi = np.log(grid[min(bi + 1, len(grid) - 1)])
        if np.max(np.abs(np.log(np.maximum(x, 1e-300)
                                / np.maximum(x_prev, 1e-300)))) < 1e-6:
            break
    fitted = dataclasses.replace(
        prior,
        peak_flops=1.0 / x[0],
        hbm_bw=1.0 / x[1],
        op_overhead_s=float(x[2]),
        collective_overhead_s=float(x[3]),
        name=prior.name if prior.name.endswith("+cal") else prior.name + "+cal")
    return CalibrationFit(profile=fitted, n_samples=len(samples),
                          mape_before=_mape(pred_before, t),
                          mape_after=_mape(A @ x, t))
