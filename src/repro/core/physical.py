"""Physical plan layer: operators the backend actually runs.

Produced from the logical IR by ``repro.core.lowering.lower``; executed by
``run`` below. Physical nodes are where realization choices live — the
logical tree never carries mode/backend/tile decisions (those are ``ir.Plan``
side-table annotations consumed at lowering time).

Operators:
  PScan            — catalog table lookup.
  PPipeline        — a fused chain of row-local stages (Filter / Project /
                     Compact), executed one table pass per stage without
                     per-node interpreter dispatch (Velox-style driver).
  PJoin/PCrossJoin — relational joins (repro.relational.ops).
  PAggregate       — group-by.
  PBlockedMatmul   — R3-1 realization: 'relational' streams the weight-tile
                     relation (paper Fig. 2); 'fused' is the pipelined blocked
                     matmul; backend 'pallas' uses the TPU kernel.
  PForestRelational— R3-2 realization: 'relational' streams the tree relation;
                     'fused' evaluates the ensemble per row.
  PRepartition     — intra-query partition boundary: converts its child's
                     row distribution (replicated / row-block / hash-bucket
                     over the mesh's data axis) into the one its consumer
                     executes under, via ``shard_map`` collectives.

Partitioning is an explicit per-node decision, not a whole-plan property:
``PhysicalPlan.parts`` is a side table (mirroring ``ir.Plan.phys``) mapping
each node's tree path to the ``PartSpec`` it executes under, and lowering
inserts ``PRepartition`` boundaries exactly where adjacent specs disagree.
Under a row partition every operator body is *unchanged* — each device runs
the ordinary single-device code on its row block; under a hash partition a
join runs on bucket-masked inputs — so partitioned execution is the same
``run_node`` with an ``axis`` name bound inside ``shard_map``
(``core.mesh.shard_replicated``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import ir
from repro.core.evaluator import as_column, eval_expr
from repro.mlfuncs.functions import MERGES
from repro.mlfuncs.registry import Registry
from repro.relational import ops
from repro.relational.table import Table


# ---------------------------------------------------------------------------
# PartSpec: how one node's rows are split over the mesh's data axis
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartSpec:
    """Row distribution of one physical node's output.

    kind : 'rep'  — replicated: every device holds all rows (the
                    single-device semantics; the default everywhere).
           'row'  — row blocks: device i holds rows
                    ``[i*ceil(C/ways), (i+1)*ceil(C/ways))`` of the
                    (tail-padded) table; local capacity is the block size.
           'hash' — hash buckets: full capacity everywhere, but device i's
                    valid mask is restricted to rows whose
                    ``hash_bucket(key) == i`` (static shapes make a
                    compacted bucket capacity unsound under skew — all keys
                    may land in one bucket — so bucket partitioning trades
                    no memory for collective-free local joins).
    """
    kind: str = "rep"
    ways: int = 1
    key: Optional[str] = None  # bucket column ('hash' only)

    def signature(self) -> str:
        if self.kind == "rep":
            return "rep"
        tag = f"{self.kind}{self.ways}"
        return tag + (f"[{self.key}]" if self.key else "")


REPLICATED = PartSpec()


# ---------------------------------------------------------------------------
# pipeline stages (row-local, fusable)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FilterStage:
    pred: ir.Expr

    def signature(self) -> str:
        return f"f[{ir._expr_sig(self.pred)}]"


@dataclasses.dataclass(frozen=True)
class ProjectStage:
    outputs: Tuple[Tuple[str, ir.Expr], ...]
    keep: Optional[Tuple[str, ...]] = None

    def signature(self) -> str:
        outs = ",".join(f"{n}={ir._expr_sig(e)}" for n, e in self.outputs)
        return f"p[{outs};{self.keep}]"


@dataclasses.dataclass(frozen=True)
class CompactStage:
    capacity: int

    def signature(self) -> str:
        return f"c[{self.capacity}]"


Stage = Union[FilterStage, ProjectStage, CompactStage]


# ---------------------------------------------------------------------------
# physical operators
# ---------------------------------------------------------------------------

class PhysNode:
    def children(self) -> Tuple["PhysNode", ...]:
        return ()


@dataclasses.dataclass(frozen=True)
class PScan(PhysNode):
    table: str


@dataclasses.dataclass(frozen=True)
class PPipeline(PhysNode):
    child: PhysNode
    stages: Tuple[Stage, ...]

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class PJoin(PhysNode):
    left: PhysNode
    right: PhysNode
    left_key: str
    right_key: str
    rprefix: str = ""

    def children(self):
        return (self.left, self.right)


@dataclasses.dataclass(frozen=True)
class PCrossJoin(PhysNode):
    left: PhysNode
    right: PhysNode
    aprefix: str = ""
    bprefix: str = ""

    def children(self):
        return (self.left, self.right)


@dataclasses.dataclass(frozen=True)
class PAggregate(PhysNode):
    child: PhysNode
    key: str
    aggs: Tuple[Tuple[str, Tuple[str, str]], ...]
    num_groups: int

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class PBlockedMatmul(PhysNode):
    child: PhysNode
    x_col: str
    out_col: str
    fn: str
    n_tiles: int
    mode: str          # 'relational' | 'fused'
    backend: str       # 'jnp' | 'pallas'
    keep: Optional[Tuple[str, ...]] = None

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class PForestRelational(PhysNode):
    child: PhysNode
    x_col: str
    out_col: str
    fn: str
    mode: str
    backend: str
    keep: Optional[Tuple[str, ...]] = None

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class PRepartition(PhysNode):
    """Partition boundary: convert the child's PartSpec into the consumer's.

    op : 'slice'     — replicated -> row: device i takes its block of the
                       tail-padded table (``out_capacity`` = block size).
      'allgather' — row -> replicated: concatenate all blocks
                       (``jax.lax.all_gather`` tiled) and drop the tail
                       padding back to ``out_capacity`` (the global
                       capacity) — row blocks tile the original row order,
                       so the reassembled table is bit-identical to the
                       unpartitioned one.
      'bucket'    — replicated -> hash: mask validity to the rows whose
                       ``hash_bucket(key) == axis_index``.
      'combine'   — hash -> replicated: zero the rows a device does not
                       own and ``psum`` columns + masks (each valid row is
                       owned by exactly one device, so the sum is exact —
                       including total skew, where one device owns all).
    """
    child: PhysNode
    op: str
    ways: int
    in_capacity: int
    out_capacity: int
    key: Optional[str] = None  # bucket column ('bucket' only)

    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class PhysicalPlan:
    root: PhysNode
    registry: Registry
    # PartSpec side table (mirrors ir.Plan.phys): node tree path -> the
    # spec the node executes under. "r" is the root, "r.0" its first
    # child, ... Empty on unpartitioned plans; purely descriptive at run
    # time (execution follows the explicit PRepartition boundaries).
    parts: Mapping[str, PartSpec] = dataclasses.field(default_factory=dict)
    ways: int = 1  # >1 iff any node's spec is partitioned

    def signature(self) -> str:
        return phys_signature(self.root)

    def part_for(self, path: str) -> PartSpec:
        return self.parts.get(path, REPLICATED)

    def part_signature(self) -> str:
        """The PartSpec vector, compact and stable (cache-key material):
        only non-replicated entries, in tree-path order."""
        items = [f"{p}={s.signature()}" for p, s in sorted(self.parts.items())
                 if s.kind != "rep"]
        return ",".join(items) if items else "rep"


def phys_signature(node: PhysNode) -> str:
    if isinstance(node, PScan):
        return f"S({node.table})"
    if isinstance(node, PPipeline):
        stages = "|".join(s.signature() for s in node.stages)
        return f"PIPE({stages};{phys_signature(node.child)})"
    if isinstance(node, PJoin):
        return (f"J({node.left_key}={node.right_key},"
                f"{phys_signature(node.left)},{phys_signature(node.right)})")
    if isinstance(node, PCrossJoin):
        return f"X({phys_signature(node.left)},{phys_signature(node.right)})"
    if isinstance(node, PAggregate):
        aggs = ",".join(f"{o}={k}:{c}" for o, (k, c) in node.aggs)
        return f"A({node.key};{aggs};{phys_signature(node.child)})"
    if isinstance(node, PBlockedMatmul):
        return (f"BM({node.x_col}->{node.out_col},{node.fn},{node.n_tiles},"
                f"{node.mode},{node.backend},{phys_signature(node.child)})")
    if isinstance(node, PForestRelational):
        return (f"FR({node.x_col}->{node.out_col},{node.fn},{node.mode},"
                f"{node.backend},{phys_signature(node.child)})")
    if isinstance(node, PRepartition):
        return (f"RP({node.op},{node.ways},{node.key},{node.in_capacity}"
                f"->{node.out_capacity},{phys_signature(node.child)})")
    raise TypeError(type(node))


# ---------------------------------------------------------------------------
# realizations of R3-1 / R3-2
# ---------------------------------------------------------------------------

def matmul_weight(registry: Registry, fn_name: str):
    fn = registry.get(fn_name)
    assert fn.graph is not None and len(fn.graph.nodes) == 1
    atom = fn.graph.nodes[0].atom
    assert atom.kind == "matmul", f"{fn_name} is not a pure matmul"
    return jnp.asarray(atom.params["w"])


def blocked_matmul_fused(x: jax.Array, w: jax.Array, n_tiles: int,
                         backend: str) -> jax.Array:
    """Pipelined tile-at-a-time matmul over column blocks of w."""
    if backend == "pallas":
        from repro.kernels.block_matmul import ops as bm_ops
        return bm_ops.block_matmul(x, w, n_tiles)
    dout = w.shape[1]
    tile = -(-dout // n_tiles)  # ceil
    pad = tile * n_tiles - dout
    wp = jnp.pad(w, ((0, 0), (0, pad)))
    tiles = wp.reshape(w.shape[0], n_tiles, tile).transpose(1, 0, 2)  # [T, din, tile]

    def body(carry, wt):
        return carry, x @ wt

    _, blocks = jax.lax.scan(body, 0, tiles)  # [T, N, tile]
    out = blocks.transpose(1, 0, 2).reshape(x.shape[0], n_tiles * tile)
    return out[:, :dout]


def blocked_matmul_relational(t: Table, x_col: str, w: jax.Array,
                              n_tiles: int) -> jax.Array:
    """Literal tensor-relational pipeline (paper Fig. 2):
    tile relation W(colId, tile) -> crossJoin -> project -> assemble.

    The crossJoin is *streamed* one tile at a time (the paper's buffer-pool
    scan / Velox pipelining): each scan step joins T with a single-tile
    relation, projects the per-pair block, and emits it; assembly
    concatenates blocks per rowId. Peak memory is one tile + one block
    column, never the full product.
    """
    din, dout = w.shape
    tile = -(-dout // n_tiles)
    pad = tile * n_tiles - dout
    wp = jnp.pad(w, ((0, 0), (0, pad)))
    tiles = wp.reshape(din, n_tiles, tile).transpose(1, 0, 2)  # [T, din, tile]
    x = t[x_col]

    def scan_tile(_, wt):
        # one-tile relation, crossJoin with T (trivially T rows), project
        one = Table.from_columns({"tile": wt.reshape(1, -1)})
        pairs = ops.cross_join(Table.from_columns({x_col: x}), one)
        wt_full = pairs["tile"].reshape(-1, din, tile)
        yblock = jnp.einsum("nd,ndk->nk", pairs[x_col], wt_full)
        return _, yblock

    _, blocks = jax.lax.scan(scan_tile, 0, tiles)      # [T, N, tile]
    out = blocks.transpose(1, 0, 2).reshape(t.capacity, n_tiles * tile)
    return out[:, :dout]


def forest_fused(x: jax.Array, fn, backend: str) -> jax.Array:
    atom = fn.graph.nodes[0].atom
    if backend == "pallas":
        from repro.kernels.decision_forest import ops as df_ops
        p = atom.params
        return df_ops.forest_predict(x, jnp.asarray(p["feat"]),
                                     jnp.asarray(p["thresh"]),
                                     jnp.asarray(p["leaf"]))
    return atom.apply(x)


def forest_relational(t: Table, x_col: str, fn) -> jax.Array:
    """crossJoin(T, DF) -> project t.predict(x) -> aggregate mean by row.

    Streamed one tree at a time (buffer-pool scan over the DF relation):
    each step joins T with a single-tree relation, projects the per-pair
    prediction, and the running aggregate accumulates the vote.
    """
    p = fn.graph.nodes[0].atom.params
    feat = jnp.asarray(p["feat"])
    thresh = jnp.asarray(p["thresh"])
    leaf = jnp.asarray(p["leaf"])
    depth = int(p["depth"])
    n_trees = feat.shape[0]
    x = t[x_col]

    def scan_tree(acc, tree):
        f, th, lv = tree
        one = Table.from_columns({"feat": f[None], "thresh": th[None], "leaf": lv[None]})
        pairs = ops.cross_join(Table.from_columns({x_col: x}), one)
        xp, fp, tp, lp = pairs[x_col], pairs["feat"], pairs["thresh"], pairs["leaf"]
        node = jnp.zeros((xp.shape[0],), jnp.int32)
        for _ in range(depth):
            fi = jnp.take_along_axis(fp, node[:, None], axis=1)[:, 0]
            ti = jnp.take_along_axis(tp, node[:, None], axis=1)[:, 0]
            xv = jnp.take_along_axis(xp, fi[:, None], axis=1)[:, 0]
            node = 2 * node + 1 + (xv > ti).astype(jnp.int32)
        leaf_idx = node - (2 ** depth - 1)
        pred = jnp.take_along_axis(lp, leaf_idx[:, None], axis=1)[:, 0]
        return acc + pred, None

    acc, _ = jax.lax.scan(scan_tree, jnp.zeros((x.shape[0],), jnp.float32),
                          (feat, thresh, leaf))
    return acc / n_trees


# ---------------------------------------------------------------------------
# repartition boundaries (shard_map collectives)
# ---------------------------------------------------------------------------

def _pad_rows(x: jax.Array, n: int):
    """Append ``n`` zero rows (False for the valid mask) at the tail."""
    if n <= 0:
        return x
    return jnp.pad(x, ((0, n),) + ((0, 0),) * (x.ndim - 1))


def run_repartition(node: PRepartition, t: Table, axis: Optional[str]) -> Table:
    from repro.core import mesh as mesh_util

    if axis is None:
        raise RuntimeError(
            f"PRepartition({node.op}) needs a mesh axis: partitioned plans "
            "execute inside shard_map (core.mesh.shard_replicated) — see "
            "PlanCache.get_or_compile_partitioned")
    i = jax.lax.axis_index(axis)
    if node.op == "slice":
        block = node.out_capacity
        pad = block * node.ways - t.capacity

        def sl(x):
            return jax.lax.dynamic_slice_in_dim(_pad_rows(x, pad), i * block,
                                                block, axis=0)

        return Table(columns={k: sl(v) for k, v in t.columns.items()},
                     valid=sl(t.valid))
    if node.op == "allgather":
        # blocks tile the (tail-padded) original row order: concatenating
        # them and slicing off the padding restores the exact global table
        def ag(x):
            return jax.lax.all_gather(x, axis, axis=0,
                                      tiled=True)[:node.out_capacity]

        return Table(columns={k: ag(v) for k, v in t.columns.items()},
                     valid=ag(t.valid))
    if node.op == "bucket":
        own = mesh_util.hash_bucket(t[node.key], node.ways) == i
        return Table(columns=t.columns, valid=t.valid & own)
    if node.op == "combine":
        # each valid row is owned by exactly one device: zero the rest and
        # psum — exact for ints, and exact for floats too (x + 0.0 == x)
        def cb(x):
            m = t.valid.reshape((-1,) + (1,) * (x.ndim - 1))
            return jax.lax.psum(jnp.where(m, x, jnp.zeros((), x.dtype)), axis)

        valid = jax.lax.psum(t.valid.astype(jnp.int32), axis) > 0
        return Table(columns={k: cb(v) for k, v in t.columns.items()},
                     valid=valid)
    raise ValueError(f"unknown repartition op {node.op!r}")


# ---------------------------------------------------------------------------
# physical execution
# ---------------------------------------------------------------------------

def _run_stage(stage: Stage, t: Table, registry: Registry) -> Table:
    if isinstance(stage, FilterStage):
        with jax.named_scope("filter"):
            mask = jnp.asarray(eval_expr(stage.pred, t, registry)).astype(bool)
            mask = as_column(mask, t.capacity)
            return ops.filter_(t, mask)
    if isinstance(stage, ProjectStage):
        with jax.named_scope("project"):
            new_cols = {name: as_column(eval_expr(e, t, registry), t.capacity)
                        for name, e in stage.outputs}
            return ops.project(t, new_cols, keep=stage.keep)
    if isinstance(stage, CompactStage):
        with jax.named_scope("compact"):
            return ops.compact(t, stage.capacity)
    raise TypeError(type(stage))


def _grid_form(e: ir.Expr, owner: Mapping[str, int], registry: Registry):
    """How a Project output over a cross join is scored from the join's two
    sides without its pairs: ``(merge atom, ((ref, args), (ref, args)))``,
    per side (left, then right) the graph value the merge reads and the
    indices of the call arguments that side computes it from; or None
    where ``e`` is not a ``Call`` whose graph ends in a ``MERGES`` atom over
    one argument from each side. ``owner`` maps the join's output columns
    to their side (0 left, 1 right)."""
    if not isinstance(e, ir.Call):
        return None
    graph = registry.get(e.fn).graph
    if graph is None:
        return None
    merge = graph.node(graph.out)
    if merge.atom.kind not in MERGES or len(merge.args) != 2:
        return None
    arg_side = []
    for a in e.args:
        sides = {owner.get(c) for c in a.cols()}
        if len(sides) != 1 or None in sides:
            return None
        arg_side.append(sides.pop())
    deps = graph.input_deps()
    per_side = {}
    for ref in merge.args:
        ins = frozenset([ref[1]]) if ref[0] == "in" else deps[ref[1]]
        sides = {arg_side[k] for k in ins}
        if len(sides) != 1:
            return None
        per_side[sides.pop()] = (ref, tuple(sorted(ins)))
    if len(per_side) != 2:
        return None
    return merge.atom, (per_side[0], per_side[1])


def score_cross_join(node: PCrossJoin, stage: ProjectStage, lt: Table,
                     rt: Table, registry: Registry) -> Optional[Table]:
    """``stage`` run on ``node``'s output, given the join's sides, without
    the pairs' vector columns, or None where ``_grid_form`` finds a stage
    output it cannot score this way.

    Each side runs its part of every call once per row of its own; the
    merge is one [Na, Nb] product (``Atom.apply_grid``), read in the cross
    join's row order (``ia * Nb + ib``). Only the columns the stage keeps,
    and the valid mask, are repeated over the pairs."""
    owner = {node.aprefix + n: 0 for n in lt.columns}
    owner.update({node.bprefix + n: 1 for n in rt.columns})  # right shadows
    forms = [_grid_form(e, owner, registry) for _, e in stage.outputs]
    if not forms or None in forms:
        return None
    sides = (lt.rename({n: node.aprefix + n for n in lt.columns}),
             rt.rename({n: node.bprefix + n for n in rt.columns}))
    with jax.named_scope("project"):
        scores = {}
        for (name, e), (merge, per_side) in zip(stage.outputs, forms):
            graph = registry.get(e.fn).graph
            a, b = (graph.apply_ref(ref, {k: jnp.asarray(
                        eval_expr(e.args[k], t, registry)) for k in args})
                    for (ref, args), t in zip(per_side, sides))
            scores[name] = merge.apply_grid(a, b).reshape(-1)
    with jax.named_scope("cross_join"):
        keep = stage.keep

        def kept(t: Table, prefix: str, side: int) -> Table:
            if keep is None:
                return t
            return t.select([n for n in t.columns
                             if prefix + n in keep and owner[prefix + n] == side])

        pairs = ops.cross_join(kept(lt, node.aprefix, 0),
                               kept(rt, node.bprefix, 1),
                               node.aprefix, node.bprefix)
    tracing.count("cross_join.scored")
    return ops.project(pairs, scores, keep=keep)


def _materialize_cross_join(node: PCrossJoin, lt: Table, rt: Table) -> Table:
    tracing.count("cross_join.materialized")
    with jax.named_scope("cross_join"):
        return ops.cross_join(lt, rt, node.aprefix, node.bprefix)


def run_node(node: PhysNode, tables: Dict[str, Table],
             registry: Registry, axis: Optional[str] = None) -> Table:
    """Run ``node``'s subtree. Each operator's own work runs inside
    ``jax.named_scope(<operator>)`` (``tracing.OPERATORS``) and its children
    outside it, so that a compiled op's innermost scope names the operator
    that did the work.

    A pipeline whose first stage projects over a cross join scores it from
    the join's sides where it can (``score_cross_join``), and otherwise
    materializes the pairs."""
    if isinstance(node, PScan):
        return tables[node.table]
    if isinstance(node, PPipeline):
        stages = node.stages
        if isinstance(node.child, PCrossJoin) and stages and isinstance(
                stages[0], ProjectStage):
            x = node.child
            lt = run_node(x.left, tables, registry, axis)
            rt = run_node(x.right, tables, registry, axis)
            t = score_cross_join(x, stages[0], lt, rt, registry)
            if t is None:
                t = _materialize_cross_join(x, lt, rt)
            else:
                stages = stages[1:]
        else:
            t = run_node(node.child, tables, registry, axis)
        for stage in stages:
            t = _run_stage(stage, t, registry)
        return t
    if isinstance(node, PJoin):
        lt = run_node(node.left, tables, registry, axis)
        rt = run_node(node.right, tables, registry, axis)
        with jax.named_scope("join"):
            return ops.fk_join(lt, rt, node.left_key, node.right_key,
                               node.rprefix)
    if isinstance(node, PCrossJoin):
        lt = run_node(node.left, tables, registry, axis)
        rt = run_node(node.right, tables, registry, axis)
        return _materialize_cross_join(node, lt, rt)
    if isinstance(node, PAggregate):
        t = run_node(node.child, tables, registry, axis)
        with jax.named_scope("aggregate"):
            return ops.aggregate(t, node.key, dict(node.aggs),
                                 node.num_groups)
    if isinstance(node, PBlockedMatmul):
        t = run_node(node.child, tables, registry, axis)
        with jax.named_scope("blocked_matmul"):
            w = matmul_weight(registry, node.fn)
            if node.mode == "relational":
                y = blocked_matmul_relational(t, node.x_col, w, node.n_tiles)
            else:
                y = blocked_matmul_fused(t[node.x_col], w, node.n_tiles,
                                         node.backend)
            return ops.project(t, {node.out_col: y}, keep=node.keep)
    if isinstance(node, PForestRelational):
        t = run_node(node.child, tables, registry, axis)
        with jax.named_scope("forest"):
            fn = registry.get(node.fn)
            if node.mode == "relational":
                y = forest_relational(t, node.x_col, fn)
            else:
                y = forest_fused(t[node.x_col], fn, node.backend)
            return ops.project(t, {node.out_col: y}, keep=node.keep)
    if isinstance(node, PRepartition):
        t = run_node(node.child, tables, registry, axis)
        with jax.named_scope("repartition"):
            return run_repartition(node, t, axis)
    raise TypeError(type(node))


def run(pplan: PhysicalPlan, tables: Dict[str, Table],
        axis: Optional[str] = None) -> Table:
    """Execute a physical plan. ``axis`` names the shard_map mesh axis a
    *partitioned* plan's repartition boundaries collect over; unpartitioned
    plans (no PRepartition nodes) ignore it."""
    return run_node(pplan.root, tables, pplan.registry, axis)
