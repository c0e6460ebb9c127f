"""Physical layer: lowering equivalence vs the reference interpreter,
pipeline fusion, a Project scored from a cross join's sides,
backend-parameterized unified evaluator (np == jnp), and the purity of the
logical IR (no physical fields on logical nodes)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import evaluator, executor, ir
from repro.core import physical as ph
from repro.core.lowering import lower
from repro.core.rules import ALL_RULES
from repro.data import workloads
from repro.mlfuncs import builders
from repro.mlfuncs.functions import Atom, MLFunction, MLGraph, MLNode
from repro.mlfuncs.registry import Registry
from repro.relational.table import Table


def assert_canonical_close(a, b, label=""):
    assert set(a) == set(b), f"{label}: schema {sorted(set(a) ^ set(b))}"
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=5e-4, atol=5e-4,
                                   err_msg=f"{label}:{k}")


# ---------------------------------------------------------------------------
# lowered execution == reference interpreter, all 12 workload templates
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(workloads.ALL_WORKLOADS))
def test_lowering_equivalence(name):
    w = workloads.ALL_WORKLOADS[name](scale=0.3)
    ref = executor.execute_reference(w.plan, w.catalog).canonical()
    out = ph.run(lower(w.plan, w.catalog), dict(w.catalog.tables)).canonical()
    assert_canonical_close(ref, out, name)
    # explicit backend override must not change results
    out_jnp = ph.run(lower(w.plan, w.catalog, backend="jnp"),
                     dict(w.catalog.tables)).canonical()
    assert_canonical_close(ref, out_jnp, f"{name}/backend=jnp")


def test_lowering_equivalence_after_physical_rules():
    """R3-1/R3-2 annotate the side table; R4-2 re-realizes via the side
    table; lowered execution must stay equivalent throughout."""
    w = workloads.analytics_q1(scale=0.3)
    base = executor.execute_reference(w.plan, w.catalog).canonical()
    plan = w.plan
    cfgs = ALL_RULES["R3-2"].configs(plan, w.catalog)
    assert cfgs, "R3-2 must apply to the forest workload"
    plan = ALL_RULES["R3-2"].apply(plan, w.catalog, cfgs[0])
    assert plan.phys, "R3-2 must annotate the physical side table"
    assert_canonical_close(base, executor.execute(plan, w.catalog).canonical(),
                           "R3-2")
    mode_cfgs = [c for c in ALL_RULES["R4-2"].configs(plan, w.catalog)
                 if c.get("kind") == "mode"]
    assert mode_cfgs, "R4-2 must offer relational->fused on the annotated node"
    plan2 = ALL_RULES["R4-2"].apply(plan, w.catalog, mode_cfgs[0])
    assert plan2.root is plan.root, "R4-2 mode change must not touch the tree"
    assert plan2.signature() != plan.signature()
    assert_canonical_close(base, executor.execute(plan2, w.catalog).canonical(),
                           "R4-2")


# ---------------------------------------------------------------------------
# pipeline fusion
# ---------------------------------------------------------------------------

def test_filter_project_chains_fuse_into_one_pipeline():
    w = workloads.analytics_q1(scale=0.3)  # Project(Filter(Filter(Scan)))
    # tree-order lowering: this test pins the fusion mechanics; the costed
    # path may additionally insert compaction stages (tests/test_costed_*)
    pplan = lower(w.plan, w.catalog, costed=False)
    root = pplan.root
    assert isinstance(root, ph.PPipeline)
    assert isinstance(root.child, ph.PScan)
    kinds = [type(s).__name__ for s in root.stages]
    # source-to-sink order: the two filters run before the project
    assert kinds == ["FilterStage", "FilterStage", "ProjectStage"]

    def count(node):
        return sum(count(c) for c in node.children()) + (
            1 if isinstance(node, ph.PPipeline) else 0)

    assert count(root) == 1


def test_pipeline_fusion_stops_at_blocking_operators():
    w = workloads.rec_q1(scale=0.3)  # joins/aggregate/crossjoin in the middle
    pplan = lower(w.plan, w.catalog, costed=False)

    def walk(node):
        yield node
        for c in node.children():
            yield from walk(c)

    nodes = list(walk(pplan.root))
    assert any(isinstance(n, ph.PCrossJoin) for n in nodes)
    assert any(isinstance(n, ph.PAggregate) for n in nodes)
    for n in nodes:
        if isinstance(n, ph.PPipeline):
            assert not isinstance(n.child, ph.PPipeline), "maximal fusion"


# ---------------------------------------------------------------------------
# unified evaluator: np backend == jnp backend
# ---------------------------------------------------------------------------

def _expr_battery():
    age = ir.Col("age")
    genre = ir.Col("genre")
    vec = ir.Col("v")
    return [
        ir.Const(3.5),
        ir.BinOp("+", age, ir.Const(1.0)),
        ir.BinOp("/", age, ir.Const(0.0)),          # guarded division
        ir.BinOp("*", vec, age),                    # vector x scalar align
        ir.Cmp(">", age, ir.Const(40.0)),
        ir.Cmp("==", genre, ir.Const(2.0)),
        ir.BoolOp("and", (ir.Cmp(">", age, ir.Const(20.0)),
                          ir.Cmp("<", age, ir.Const(60.0)))),
        ir.BoolOp("not", (ir.Cmp(">", age, ir.Const(40.0)),)),
        ir.IsIn(genre, (1, 3)),
        ir.IfExpr(ir.Cmp(">", age, ir.Const(40.0)), age,
                  ir.BinOp("-", ir.Const(0.0), age)),
    ]


def test_unified_evaluator_np_matches_jnp():
    rng = np.random.default_rng(0)
    cols = {"age": rng.uniform(18, 80, 32).astype(np.float32),
            "genre": rng.integers(0, 5, 32).astype(np.int32),
            "v": rng.standard_normal((32, 4)).astype(np.float32)}
    t = Table.from_columns(cols)
    reg = Registry()
    for i, e in enumerate(_expr_battery()):
        a = evaluator.eval_expr(e, cols, reg, xp=np)
        b = np.asarray(evaluator.eval_expr(e, t, reg, xp=jnp))
        np.testing.assert_allclose(np.broadcast_to(a, b.shape), b,
                                   rtol=1e-5, atol=1e-6, err_msg=f"expr {i}")


def test_unified_evaluator_np_matches_jnp_on_workload_predicates():
    """Scan-level call-free predicates of every workload template evaluate
    identically under both array namespaces."""
    checked = 0
    for name in sorted(workloads.ALL_WORKLOADS):
        w = workloads.ALL_WORKLOADS[name](scale=0.3)
        for node in ir.walk(w.plan.root):
            if not (isinstance(node, ir.Filter) and isinstance(node.child, ir.Scan)
                    and not evaluator.has_call(node.pred)):
                continue
            npt = w.catalog.np_tables[node.child.table]
            tbl = w.catalog.tables[node.child.table]
            a = evaluator.eval_expr(node.pred, npt, w.plan.registry, xp=np)
            b = np.asarray(evaluator.eval_expr(node.pred, tbl, w.plan.registry))
            np.testing.assert_array_equal(np.broadcast_to(a, b.shape), b,
                                          err_msg=f"{name}")
            checked += 1
    assert checked >= 3


def test_const_evaluates_to_scalar():
    reg = Registry()
    v = evaluator.eval_expr(ir.Const(2.5), {}, reg)
    assert getattr(v, "ndim", None) == 0  # no (capacity,) materialization
    t = Table.from_columns({"x": jnp.arange(8, dtype=jnp.float32)})
    col = evaluator.as_column(v, t.capacity)
    assert col.shape == (8,)


def test_call_expr_np_namespace():
    reg = Registry()
    reg.register(builders.ffnn("f", [4, 8, 1], seed=0))
    x = np.random.default_rng(1).standard_normal((16, 4)).astype(np.float32)
    e = ir.Call("f", (ir.Col("x"),))
    a = evaluator.eval_expr(e, {"x": x}, reg, xp=np)
    b = evaluator.eval_expr(e, Table.from_columns({"x": x}), reg, xp=jnp)
    assert isinstance(a, np.ndarray)
    np.testing.assert_allclose(a, np.asarray(b), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# logical IR purity
# ---------------------------------------------------------------------------

def test_logical_nodes_carry_no_physical_fields():
    for cls in (ir.BlockedMatmul, ir.ForestRelational):
        names = {f.name for f in dataclasses.fields(cls)}
        assert not names & {"mode", "backend", "n_tiles"}, cls


def test_phys_annotation_survives_subtree_rewrites():
    """Rewrites below an annotated node rebuild it via with_children; the
    uid (and thus the side-table annotation) must survive."""
    node = ir.ForestRelational(ir.Scan("t"), x_col="x", out_col="y", fn="f")
    rebuilt = node.with_children((ir.Filter(ir.Scan("t"),
                                            ir.Cmp(">", ir.Col("x"),
                                                   ir.Const(0.0))),))
    assert rebuilt.uid == node.uid
    plan = ir.Plan(node, Registry(),
                   {node.uid: ir.PhysConfig(mode="relational")})
    assert plan.phys_for(rebuilt).mode == "relational"


# ---------------------------------------------------------------------------
# a Project over a cross join, scored from the join's sides
# ---------------------------------------------------------------------------

SCORED, MATERIALIZED = "cross_join.scored", "cross_join.materialized"


def _paths():
    return tracing.counts[SCORED], tracing.counts[MATERIALIZED]


def _assert_same_rows(ref, out, label):
    """The same rows in the same order: the valid mask exactly, every
    column on the valid rows."""
    assert set(ref.columns) == set(out.columns), label
    np.testing.assert_array_equal(np.asarray(ref.valid), np.asarray(out.valid),
                                  err_msg=f"{label}.valid")
    m = np.asarray(ref.valid)
    assert m.any(), label
    for k in ref.columns:
        np.testing.assert_allclose(np.asarray(ref[k])[m], np.asarray(out[k])[m],
                                   rtol=1e-5, atol=1e-5, err_msg=f"{label}:{k}")


@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("name", ["rec_q1", "rec_q3", "retail_q3"])
def test_scored_cross_join_equals_reference(name, batch):
    w = workloads.ALL_WORKLOADS[name](scale=0.3)
    pplan = lower(w.plan, w.catalog)
    tabs = workloads.rolled_instances(dict(w.catalog.tables), batch)
    before = _paths()
    if batch == 1:
        outs = [jax.jit(lambda t: ph.run(pplan, t))(tabs[0])]
    else:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *tabs)
        out = jax.jit(jax.vmap(lambda t: ph.run(pplan, t)))(stacked)
        outs = [jax.tree.map(lambda x, i=i: x[i], out) for i in range(batch)]
    assert _paths() == (before[0] + 1, before[1]), name
    for i, (t, out) in enumerate(zip(tabs, outs)):
        ref = executor.execute_node(w.plan.root, t, w.plan.registry,
                                    w.plan.phys).canonical()
        assert len(next(iter(ref.values()))), name
        assert_canonical_close(ref, out.canonical(), f"{name}[{i}]")


def _two_tower(merge: str) -> MLFunction:
    """A two-tower whose towers end in ``merge``."""
    g = builders.two_tower("tt", [6, 8, 5], [4, 8, 5], seed=3).graph
    out = g.node(g.out)
    nodes = [n for n in g.nodes if n is not out]
    return MLFunction(f"tt_{merge}", MLGraph(
        nodes + [MLNode(out.id, Atom(merge), out.args)], g.out, 2),
        n_inputs=2)


def _factorized() -> MLFunction:
    """R2-1's shape: cossim(relu(u·Wu + m·Wm), m), a merge argument that
    reads both sides."""
    rng = np.random.default_rng(4)

    def mm(i, o):
        return Atom("matmul", {"w": rng.standard_normal((i, o)).astype(
            np.float32)})

    nodes = [MLNode(0, mm(6, 4), (("in", 0),)),
             MLNode(1, mm(4, 4), (("in", 1),)),
             MLNode(2, Atom("add"), (("node", 0), ("node", 1))),
             MLNode(3, Atom("act", {"fn": "relu"}), (("node", 2),)),
             MLNode(4, Atom("cossim"), (("node", 3), ("in", 1)))]
    return MLFunction("fact", MLGraph(nodes, 4, 2), n_inputs=2)


def _call(fn, *cols):
    return ir.Call(fn, tuple(ir.Col(c) for c in cols))


_POSITIVE = ir.Cmp(">", ir.Col("x"), ir.Const(0.0))
# name: (stages over the cross join, source to sink; bprefix; path taken)
_XJOIN_CASES = {
    "cossim": ([ph.ProjectStage((("s", _call("tt_cossim", "u", "r_m")),),
                                keep=("id", "r_y"))], "r_", SCORED),
    "dot": ([ph.ProjectStage((("s", _call("tt_dot", "u", "r_m")),),
                             keep=("x", "r_id"))], "r_", SCORED),
    # no prefixes: the right side's ``id`` shadows the left's
    "keep-none": ([ph.ProjectStage((("s", _call("tt_cossim", "u", "m")),))],
                  "", SCORED),
    "later-stages": ([ph.ProjectStage((("s", _call("tt_dot", "u", "r_m")),
                                       ("c", _call("tt_cossim", "u", "r_m"))),
                                      keep=("x", "r_y")),
                      ph.FilterStage(ir.Cmp(">", ir.Col("s"), ir.Const(0.0))),
                      ph.ProjectStage((("z", ir.BinOp("*", ir.Col("c"),
                                                      ir.Col("x"))),),
                                      keep=("s",))], "r_", SCORED),
    "dist": ([ph.ProjectStage((("s", _call("tt_dist", "u", "r_m")),),
                              keep=("id",))], "r_", MATERIALIZED),
    "filter-first": ([ph.FilterStage(_POSITIVE),
                      ph.ProjectStage((("s", _call("tt_cossim", "u", "r_m")),),
                                      keep=("id",))], "r_", MATERIALIZED),
    "factorized": ([ph.ProjectStage((("s", _call("fact", "u", "r_m")),),
                                    keep=("id", "r_id"))], "r_", MATERIALIZED),
}


@pytest.mark.parametrize("case", sorted(_XJOIN_CASES))
def test_project_on_a_cross_join_equals_reference(case):
    stages, bprefix, path = _XJOIN_CASES[case]
    reg = Registry()
    for fn in (_two_tower("cossim"), _two_tower("dot"), _two_tower("dist"),
               _factorized()):
        reg.register(fn)
    rng = np.random.default_rng(11)

    def side(n, d, key, vec, scalar):
        valid = rng.random(n) < 0.7
        valid[0], valid[-1] = True, False  # invalid rows on both sides
        return Table.from_columns(
            {"id": jnp.arange(n, dtype=jnp.int32) + key,
             vec: jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
             scalar: jnp.asarray(rng.standard_normal(n), jnp.float32)},
            valid=valid)

    tables = {"L": side(9, 6, 0, "u", "x"), "R": side(7, 4, 100, "m", "y")}
    logical = ir.CrossJoin(ir.Scan("L"), ir.Scan("R"), "", bprefix)
    for s in stages:
        logical = (ir.Filter(logical, s.pred) if isinstance(s, ph.FilterStage)
                   else ir.Project(logical, s.outputs, s.keep))
    ref = executor.execute_node(logical, tables, reg)
    pplan = ph.PhysicalPlan(ph.PPipeline(
        ph.PCrossJoin(ph.PScan("L"), ph.PScan("R"), "", bprefix),
        tuple(stages)), reg)
    before = _paths()
    out = jax.jit(lambda t: ph.run(pplan, t))(tables)
    moved = (1, 0) if path == SCORED else (0, 1)
    assert _paths() == (before[0] + moved[0], before[1] + moved[1]), case
    _assert_same_rows(ref, out, case)


def test_scored_cross_join_compiles_to_no_pair_width_array():
    """rec_q1's compiled plan, single and vmapped, holds the score grid but
    no array of pairs x a tower width: neither side's features nor any
    tower layer is repeated over the pairs."""
    w = workloads.rec_q1(scale=0.25)
    pplan = lower(w.plan, w.catalog)
    x = pplan.root.child
    assert isinstance(x, ph.PCrossJoin)
    tables = dict(w.catalog.tables)
    na, nb = (jax.eval_shape(lambda t, n=n: ph.run_node(n, t, pplan.registry),
                             tables).capacity for n in (x.left, x.right))
    widths = {d for n in pplan.registry.get("two_tower").graph.nodes
              if n.atom.kind == "matmul" for d in n.atom.params["w"].shape}
    run = lambda t: ph.run(pplan, t)  # noqa: E731
    for batch, f, args in (
            (1, run, tables),
            (4, jax.vmap(run), jax.tree.map(lambda *xs: jnp.stack(xs),
                                            *[tables] * 4))):
        text = jax.jit(f).lower(args).compile().as_text()
        shapes = {tuple(int(d) for d in dims.split(",") if d) for dims in
                  re.findall(r"\b[a-z]+[0-9]*\[([0-9,]*)\]", text)}
        pairs = batch * na * nb
        assert any(np.prod(s) == pairs for s in shapes), (batch, na, nb)
        # one axis a tower width, the others the pairs
        wide = [s for s in shapes for i, d in enumerate(s)
                if d in widths and np.prod(s[:i] + s[i + 1:]) == pairs]
        assert not wide, (batch, na, nb, sorted(widths), wide)
