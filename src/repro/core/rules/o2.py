"""O2 — factorized inference (paper R2-1/R2-3, Morpheus/LMFAO lineage).

R2-1 rewrites matMul(concat(x_S, x_R), W) into
matMul(x_S, W_S) + matMul(x_R, W_R) inside the bottom-level IR. The partial
matmuls then become independent single-input subgraphs, which R4-1-split +
R1-3 push below the join — eliminating the redundant compute the join's
row replication would cause (paper Fig. 1 / Fig. 12(d)).

Over a cross join the same factorization has one more step to make it pay:
every part of the ML calls above the join (a Filter's predicate or a
Project's outputs, through row-local operators) that reads one side only is
computed below the join, once per row of that side, and the pairs carry
only what the calls still need (``_separate_cross_join``).

R2-3 factorizes Euclidean distance over concatenated features:
dist([a,b],[c,d]) = sqrt(dist(a,c)^2 + dist(b,d)^2).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from repro.core import ir
from repro.core.rules import base
from repro.core.rules.base import Rule, RuleConfig, register_rule
from repro.mlfuncs.functions import Atom, MLFunction, MLGraph, MLNode


def _project_calls(plan, catalog):
    """Yield (path, out_name, call_expr, child_schema) for Project outputs
    that are direct Calls."""
    for p in base.all_paths(plan.root):
        n = base.node_at(plan.root, p)
        if not isinstance(n, ir.Project):
            continue
        ci = ir.infer(n.child, plan.registry, catalog)
        for name, e in n.outputs:
            if isinstance(e, ir.Call):
                yield p, name, e, ci.schema


def _concat_matmul_nodes(g: MLGraph):
    """Yield (concat_node, matmul_node) pairs where matmul consumes a concat
    of graph inputs."""
    by_id = {n.id: n for n in g.nodes}
    for n in g.nodes:
        if n.atom.kind != "matmul" or len(n.args) != 1:
            continue
        r = n.args[0]
        if r[0] != "node":
            continue
        c = by_id[r[1]]
        if c.atom.kind != "concat":
            continue
        if all(a[0] == "in" for a in c.args):
            yield c, n


@register_rule
class FactorizeLinear(Rule):
    name = "R2-1"
    category = "O2"

    def configs(self, plan, catalog):
        # one config per cross join whose calls above factorize: it does the
        # whole separation at once, so that a search of a few steps finds it
        out = [RuleConfig.make(self.name, path=p, kind="cross")
               for p in base.all_paths(plan.root)
               if isinstance(base.node_at(plan.root, p), ir.CrossJoin)
               and _factorizes_across(plan, catalog, p)]
        for p, name, call, schema in _project_calls(plan, catalog):
            fn = plan.registry.get(call.fn)
            if fn.graph is None or fn.n_inputs < 2:
                continue
            for c, m in _concat_matmul_nodes(fn.graph):
                out.append(RuleConfig.make(self.name, path=p, output=name,
                                           fn=call.fn, matmul=m.id))
        return out

    def apply(self, plan, catalog, cfg):
        if cfg.get("kind") == "cross":
            out = _separate_cross_join(plan, catalog, cfg.get("path"))
            if out is None:
                raise ValueError("nothing above this cross join factorizes")
            return out
        registry = plan.registry.copy()
        fn = registry.get(cfg.get("fn"))
        g = fn.graph
        m = g.node(cfg.get("matmul"))
        c = g.node(m.args[0][1])
        # find the Call site to learn input dims
        proj = base.node_at(plan.root, cfg.get("path"))
        call = dict(proj.outputs)[cfg.get("output")]
        schema = ir.infer(proj.child, registry, catalog).schema
        in_dims = [max(ir.expr_dim(a, schema, registry), 1) for a in call.args]
        g2 = _split_matmul(g, m, c, [in_dims[r[1]] for r in c.args])
        new_name = registry.fresh_name(fn.name + "_fact")
        registry.replace(dataclasses.replace(fn, name=new_name, graph=g2))
        new_call = ir.Call(new_name, call.args)
        outs = tuple((n2, new_call if n2 == cfg.get("output") else e2)
                     for n2, e2 in proj.outputs)
        new_proj = dataclasses.replace(proj, outputs=outs)
        root = base.replace_at(plan.root, cfg.get("path"), new_proj)
        return ir.Plan(root, registry, plan.phys)


@register_rule
class FactorizeDistance(Rule):
    """R2-3: dist(concat(a,b), concat(c,d)) -> sqrt(d(a,c)^2 + d(b,d)^2)."""
    name = "R2-3"
    category = "O2"

    def configs(self, plan, catalog):
        out = []
        for p, name, call, schema in _project_calls(plan, catalog):
            fn = plan.registry.get(call.fn)
            if fn.graph is None:
                continue
            by_id = {n.id: n for n in fn.graph.nodes}
            for n in fn.graph.nodes:
                if n.atom.kind != "dist" or len(n.args) != 2:
                    continue
                if not all(r[0] == "node" and by_id[r[1]].atom.kind == "concat"
                           for r in n.args):
                    continue
                ca, cb = by_id[n.args[0][1]], by_id[n.args[1][1]]
                if len(ca.args) == len(cb.args) and all(
                        r[0] == "in" for r in ca.args + cb.args):
                    out.append(RuleConfig.make(self.name, path=p, output=name,
                                               fn=call.fn, dist=n.id))
        return out

    def apply(self, plan, catalog, cfg):
        registry = plan.registry.copy()
        fn = registry.get(cfg.get("fn"))
        g = fn.graph
        n = g.node(cfg.get("dist"))
        by_id = {x.id: x for x in g.nodes}
        ca, cb = by_id[n.args[0][1]], by_id[n.args[1][1]]
        new_nodes: List[MLNode] = []
        nid = g.fresh_id()
        sq_refs = []
        for ra, rb in zip(ca.args, cb.args):
            new_nodes.append(MLNode(id=nid, atom=Atom("dist"), args=(ra, rb)))
            dref = ("node", nid)
            nid += 1
            new_nodes.append(MLNode(id=nid, atom=Atom("mul"), args=(dref, dref)))
            sq_refs.append(("node", nid))
            nid += 1
        acc = sq_refs[0]
        for ref in sq_refs[1:]:
            new_nodes.append(MLNode(id=nid, atom=Atom("add"), args=(acc, ref)))
            acc = ("node", nid)
            nid += 1
        new_nodes.append(MLNode(id=nid, atom=Atom("sqrt"), args=(acc,)))
        g2 = base.replace_graph_node(g, n.id, new_nodes, nid)
        g2 = _prune_unused(g2)
        new_name = registry.fresh_name(fn.name + "_dfact")
        registry.replace(dataclasses.replace(fn, name=new_name, graph=g2))
        proj = base.node_at(plan.root, cfg.get("path"))
        call = dict(proj.outputs)[cfg.get("output")]
        outs = tuple((n2, ir.Call(new_name, call.args) if n2 == cfg.get("output") else e2)
                     for n2, e2 in proj.outputs)
        root = base.replace_at(plan.root, cfg.get("path"),
                               dataclasses.replace(proj, outputs=outs))
        return ir.Plan(root, registry, plan.phys)


def _split_matmul(g: MLGraph, m: MLNode, c: MLNode, arg_dims,
                  keys=None) -> MLGraph:
    """R2-1 on ``m``, a matmul of the concat ``c``: a partial matmul per
    concat argument over its ``arg_dims`` rows of the weight, and their
    sum in place of ``m``. The parts of one ``keys`` value are summed
    first, then those sums in key order (each part its own key where none
    are given); the concat goes where nothing else reads it."""
    w = np.asarray(m.atom.params["w"])
    keys = range(len(c.args)) if keys is None else keys
    nid = g.fresh_id()
    new_nodes: List[MLNode] = []
    groups: Dict = {}
    off = 0
    for r, d, k in zip(c.args, arg_dims, keys):
        new_nodes.append(MLNode(id=nid, atom=Atom(
            "matmul", {"w": w[off:off + d].copy()}), args=(r,)))
        groups.setdefault(k, []).append(("node", nid))
        nid, off = nid + 1, off + d
    assert off == w.shape[0], f"weight rows {w.shape[0]} != concat dim {off}"

    def total(refs):
        nonlocal nid
        acc = refs[0]
        for ref in refs[1:]:
            new_nodes.append(MLNode(id=nid, atom=Atom("add"), args=(acc, ref)))
            acc, nid = ("node", nid), nid + 1
        return acc

    acc = total([total(groups[k]) for k in sorted(groups)])
    return _prune_unused(base.replace_graph_node(g, m.id, new_nodes, acc[1]))


def _prune_unused(g: MLGraph) -> MLGraph:
    needed = set()
    stack = [g.out]
    while stack:
        cur = stack.pop()
        if cur in needed:
            continue
        needed.add(cur)
        for r in g.node(cur).args:
            if r[0] == "node":
                stack.append(r[1])
    return MLGraph(nodes=[n for n in g.nodes if n.id in needed], out=g.out,
                   n_inputs=g.n_inputs)


# ---------------------------------------------------------------------------
# R2-1 over a cross join: factorize, then compute each side's part below it
# ---------------------------------------------------------------------------

_ROW_LOCAL = (ir.Filter, ir.Project, ir.Compact)
_BOTH = frozenset((0, 1))


def _inline(call: ir.Call, registry) -> Tuple[MLGraph, List[ir.Expr]]:
    """The call's graph with every argument that is itself a call of a
    graph function inlined, and the remaining argument expressions (the
    graph's inputs, in order)."""
    leaves: List[ir.Expr] = []
    nodes: List[MLNode] = []

    def build(c: ir.Call):
        g = registry.get(c.fn).graph
        refs = []
        for a in c.args:
            if isinstance(a, ir.Call) and registry.get(a.fn).graph is not None:
                refs.append(build(a))
            else:
                refs.append(("in", len(leaves)))
                leaves.append(a)
        ids = {}
        for n in g.nodes:
            ids[n.id] = len(nodes)
            nodes.append(MLNode(id=len(nodes), atom=n.atom, args=tuple(
                refs[r[1]] if r[0] == "in" else ("node", ids[r[1]])
                for r in n.args)))
        return ("node", ids[g.out])

    out = build(call)
    return MLGraph(nodes=nodes, out=out[1], n_inputs=len(leaves)), leaves


def _sides_of(g: MLGraph, in_sides) -> Dict[int, frozenset]:
    """Node id -> the cross join's sides (0 left, 1 right) it reads."""
    deps: Dict[int, frozenset] = {}
    for n in g.nodes:
        s = frozenset()
        for r in n.args:
            s |= in_sides[r[1]] if r[0] == "in" else deps[r[1]]
        deps[n.id] = s
    return deps


def _ref_sides(r, in_sides, deps) -> frozenset:
    return in_sides[r[1]] if r[0] == "in" else deps[r[1]]


def _concat_across(g: MLGraph, in_sides):
    """The first (concat, matmul) of ``g`` where the matmul reads a concat
    whose parts each read at most one side, and not all the same one; None
    where there is none."""
    deps = _sides_of(g, in_sides)
    by_id = {n.id: n for n in g.nodes}
    for m in g.nodes:
        if (m.atom.kind != "matmul" or len(m.args) != 1
                or m.args[0][0] != "node"):
            continue
        c = by_id[m.args[0][1]]
        parts = [_ref_sides(r, in_sides, deps) for r in c.args]
        if (c.atom.kind == "concat" and all(len(p) <= 1 for p in parts)
                and len(frozenset().union(*parts)) == 2):
            return c, m
    return None


def _factorize_across(g: MLGraph, in_sides, in_dims) -> Tuple[MLGraph, int]:
    """R2-1 inside ``g`` wherever a matmul reads a concat of both sides
    (``_concat_across``), the parts of one side summed first, so that each
    side's sum is a subgraph of that side alone. Returns the graph and the
    matmuls split."""
    split = 0
    while (found := _concat_across(g, in_sides)) is not None:
        c, m = found
        deps, dims = _sides_of(g, in_sides), g.infer_dims(in_dims)
        g = _split_matmul(
            g, m, c,
            [max(in_dims[r[1]] if r[0] == "in" else dims[r[1]], 1)
             for r in c.args],
            [min(_ref_sides(r, in_sides, deps), default=0) for r in c.args])
        split += 1
    return g, split


class _Separation:
    """One pass of R2-1 over the row-local operators above a cross join."""

    def __init__(self, plan, schemas):
        self.registry = plan.registry.copy()
        # column -> side it is computed on (0, 1), or both for pair columns
        self.side = {c: frozenset((s,)) for s, sch in enumerate(schemas)
                     for c in sch}
        self.dims = {c: d for sch in schemas for c, d in sch.items()}
        self.pushed: List[List[Tuple[str, ir.Expr]]] = [[], []]
        self.factorized = 0

    def expr_side(self, e: ir.Expr) -> frozenset:
        s = frozenset()
        for c in e.cols():
            s |= self.side.get(c, _BOTH)
        return s

    def push(self, side: int, name: str, e: ir.Expr) -> None:
        self.pushed[side].append((name, e))
        self.side[name] = frozenset((side,))
        self.dims[name] = ir.expr_dim(e, self.dims, self.registry)

    def expr(self, e: ir.Expr) -> ir.Expr:
        """``e`` with each graph call split into per-side columns (pushed)
        and a residual over them."""
        if isinstance(e, ir.Call):
            if self.registry.get(e.fn).graph is not None:
                return self.call(e)
            return e
        if isinstance(e, (ir.Cmp, ir.BinOp)):
            return dataclasses.replace(e, a=self.expr(e.a), b=self.expr(e.b))
        if isinstance(e, ir.BoolOp):
            return dataclasses.replace(
                e, args=tuple(self.expr(a) for a in e.args))
        if isinstance(e, ir.IsIn):
            return dataclasses.replace(e, a=self.expr(e.a))
        if isinstance(e, ir.IfExpr):
            return ir.IfExpr(self.expr(e.cond), self.expr(e.t),
                             self.expr(e.f))
        return e

    def call(self, call: ir.Call) -> ir.Expr:
        g, leaves = _inline(call, self.registry)
        in_sides = [self.expr_side(a) for a in leaves]
        in_dims = [ir.expr_dim(a, self.dims, self.registry) for a in leaves]
        g, n = _factorize_across(g, in_sides, in_dims)
        self.factorized += n
        deps = _sides_of(g, in_sides)
        if len(deps[g.out]) == 1:  # the whole call reads one side
            side = min(deps[g.out])
            name = base.fresh_col(call.fn)
            self.push(side, name, self._register(call.fn + "_side", g,
                                                 leaves))
            return ir.Col(name)
        users = base.graph_users(g)
        cuts = [n.id for n in g.nodes if len(deps[n.id]) == 1
                and any(deps[u] == _BOTH for u in users[n.id])]
        if not cuts:
            return call if not n else self._register(call.fn + "_fact", g,
                                                     leaves)
        args = list(leaves)
        res = g
        for cut in cuts:
            sub, order = base.extract_subgraph(g, cut)
            name = base.fresh_col(call.fn)
            self.push(min(deps[cut]), name, self._register(
                call.fn + "_side", sub, [leaves[i] for i in order]))
            res = base.residual_graph(res, cut, new_input=len(args))
            args.append(ir.Col(name))
        res, used = base.drop_unused_inputs(res)
        return self._register(call.fn + "_fact", res,
                              [args[i] for i in used])

    def _register(self, base_name: str, g: MLGraph, args) -> ir.Call:
        name = self.registry.fresh_name(base_name)
        self.registry.replace(MLFunction(name=name, graph=g,
                                         n_inputs=g.n_inputs))
        return ir.Call(name, tuple(args))


def _needed_below(node: ir.RelNode, needed: set) -> set:
    """Columns a row-local node reads from its child to give ``needed``."""
    if isinstance(node, ir.Filter):
        return needed | node.pred.cols()
    if isinstance(node, ir.Project):
        made = {n for n, _ in node.outputs}
        out = set().union(*(e.cols() for _, e in node.outputs))
        return out | (needed - made)
    return set(needed)


def _chain_above(root: ir.RelNode, path):
    """The Filter/Project/Compact nodes directly above the node at
    ``path``, bottom-up, and the path of the topmost."""
    chain, p = [], tuple(path)
    while p and isinstance(base.node_at(root, p[:-1]), _ROW_LOCAL):
        p = p[:-1]
        chain.append(base.node_at(root, p))
    return chain, p


def _pushed(side: Dict[str, frozenset], name: str, e: ir.Expr,
            e_side: frozenset) -> bool:
    """Whether Project output ``name = e``, which reads ``e_side``, moves
    below the join as it is: a call of one side's columns only."""
    return (len(e_side) == 1 and name not in side
            and next(base.expr_calls(e), None) is not None)


def _factorizes_across(plan: ir.Plan, catalog: ir.Catalog, path) -> bool:
    """Whether a call in the chain above the cross join at ``path`` has a
    matmul over a concat of both sides: where ``_separate_cross_join``
    applies. Reads the graphs; rewrites nothing."""
    join = base.node_at(plan.root, path)
    if join.aprefix or join.bprefix:
        return False
    side = {c: frozenset((s,)) for s, child in enumerate(join.children())
            for c in ir.infer(child, plan.registry, catalog).schema}

    def of(e: ir.Expr) -> frozenset:
        return frozenset().union(*(side.get(c, _BOTH) for c in e.cols()))

    for node in _chain_above(plan.root, path)[0]:
        exprs = ([node.pred] if isinstance(node, ir.Filter) else
                 [e for _, e in getattr(node, "outputs", ())])
        for call in (c for e in exprs for c in base.expr_calls(e)):
            if plan.registry.get(call.fn).graph is not None:
                g, leaves = _inline(call, plan.registry)
                if _concat_across(g, [of(a) for a in leaves]) is not None:
                    return True
        for name, e in getattr(node, "outputs", ()):
            s = of(e)
            side[name] = s if _pushed(side, name, e, s) else _BOTH
    return False


def _separate_cross_join(plan: ir.Plan, catalog: ir.Catalog, path):
    """The plan with the calls above the cross join at ``path`` separated
    by side (R2-1 plus the pushdown that makes it pay), or None where no
    matmul there reads both sides through a concat.

    Bottom-up through the Filter/Project/Compact chain directly above the
    join: a Project output that reads one side moves below the join as
    it is; any other call is inlined (its graph-function arguments
    included), factorized wherever a matmul reads a concat of both sides,
    and cut at every node that reads one side and feeds a node reading
    both; each cut becomes a column computed below the join, and the call
    a residual over those columns. Then every keep above the join is cut
    to what the chain's output needs (the new columns pass through), and
    each side keeps only the columns the pairs read, so that nothing else
    rides them. The chain's output schema is unchanged."""
    root = plan.root
    join = base.node_at(root, path)
    chain, p = _chain_above(root, path)
    if join.aprefix or join.bprefix or not chain:
        return None
    schemas = [ir.infer(c, plan.registry, catalog).schema
               for c in join.children()]
    sep = _Separation(plan, schemas)
    layers = [[], []]  # per side: one Project of pushed columns per node
    new_chain = []
    for node in chain:
        if isinstance(node, ir.Filter):
            node = dataclasses.replace(node, pred=sep.expr(node.pred))
        elif isinstance(node, ir.Project):
            outs, keep = [], node.keep
            for name, e in node.outputs:
                side = sep.expr_side(e)
                if _pushed(sep.side, name, e, side):
                    sep.push(min(side), name, e)
                    keep = None if keep is None else tuple(keep) + (name,)
                else:
                    outs.append((name, sep.expr(e)))
                    sep.side[name] = _BOTH
                    sep.dims[name] = ir.expr_dim(e, sep.dims, plan.registry)
            node = ir.Project(node.child, outputs=tuple(outs), keep=keep)
        for s in (0, 1):
            if sep.pushed[s]:
                layers[s].append(tuple(sep.pushed[s]))
                sep.pushed[s] = []
        new_chain.append(node)
    if not sep.factorized:
        return None
    # the chain's output keeps its schema; each keep below it is cut to
    # what is needed above
    want = set(ir.infer(chain[-1], plan.registry, catalog).schema)
    needed = set(want)
    for i in range(len(new_chain) - 1, -1, -1):
        node = new_chain[i]
        if isinstance(node, ir.Project) and node.keep is not None:
            made = {n for n, _ in node.outputs}
            node = dataclasses.replace(node,
                                       keep=tuple(sorted(needed - made)))
        new_chain[i] = node
        needed = _needed_below(node, needed)
    sides = []
    for s, child in enumerate(join.children()):
        for outs in layers[s]:
            child = ir.Project(child, outputs=outs, keep=None)
        have = set(schemas[s]) | {n for outs in layers[s] for n, _ in outs}
        if have - needed:
            child = ir.Project(child, outputs=(),
                               keep=tuple(sorted(have & needed)))
        sides.append(child)
    top: ir.RelNode = join.with_children(sides)
    for node in new_chain:
        top = node.with_children((top,))
    if set(ir.infer(top, sep.registry, catalog).schema) != want:
        top = ir.Project(top, outputs=(), keep=tuple(sorted(want)))
    return ir.Plan(base.replace_at(root, p, top), sep.registry, plan.phys)
