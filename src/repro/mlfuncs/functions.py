"""Atomic ML functions, computation graphs, and high-level ML functions.

An ``Atom`` is a batched primitive (operates on [N, d] / [N] columns). Every
atom exposes ``out_dim`` and ``flops_per_row`` so the query optimizer can read
tensor shapes and costs straight off the bottom-level IR (paper Sec. III-C).

``MLGraph`` is the bottom-level IR: nodes are atoms, edges are tensors. Graph
inputs are vector/scalar columns of the enclosing relation.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Ref = Tuple[str, int]  # ('in', k) or ('node', node_id)


def _act(kind: str, x: jax.Array) -> jax.Array:
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "sigmoid":
        return jax.nn.sigmoid(x)
    if kind == "tanh":
        return jnp.tanh(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "softmax":
        return jax.nn.softmax(x, axis=-1)
    if kind == "squared_relu":
        return jnp.square(jax.nn.relu(x))
    if kind == "identity":
        return x
    raise ValueError(f"unknown activation {kind}")


@dataclasses.dataclass
class Atom:
    """One atomic ML function instance (with bound parameters)."""

    kind: str
    params: Dict[str, object] = dataclasses.field(default_factory=dict)
    # execution backend, mutated by R4-2 (library replacement): 'jnp'|'pallas'
    backend: str = "jnp"

    # -- shape/flops introspection (dims: 0 means scalar/int column) ------
    def out_dim(self, in_dims: Sequence[int]) -> int:
        k, p = self.kind, self.params
        if k == "matmul":
            return int(p["w"].shape[1])
        if k == "bias":
            return in_dims[0]
        if k == "act":
            return in_dims[0]
        if k == "concat":
            return int(sum(max(d, 1) for d in in_dims))
        if k in ("cossim", "dot", "dist"):
            return 0
        if k == "embed":
            return int(p["table"].shape[1])
        if k == "scale":
            return in_dims[0]
        if k == "onehot":
            return int(p["num"])
        if k == "forest":
            return 0
        if k == "fused_dense":
            return int(p["w"].shape[1])
        if k == "binarize":
            return 0
        if k == "slice":
            return int(p["stop"] - p["start"])
        if k in ("add", "mul", "sqrt"):
            return in_dims[0]
        if k == "argmin":
            return 0
        if k == "const_vec":
            return int(np.asarray(p["value"]).shape[-1])
        raise ValueError(f"unknown atom kind {k}")

    def flops_per_row(self, in_dims: Sequence[int]) -> float:
        k, p = self.kind, self.params
        d = [max(x, 1) for x in in_dims] if in_dims else [1]
        if k == "matmul":
            w = p["w"]
            return 2.0 * w.shape[0] * w.shape[1]
        if k == "fused_dense":
            w = p["w"]
            return 2.0 * w.shape[0] * w.shape[1] + 2.0 * w.shape[1]
        if k in ("bias", "act", "scale", "add", "mul", "sqrt", "binarize", "argmin"):
            return float(d[0])
        if k == "concat":
            return float(sum(d))
        if k in ("cossim", "dist"):
            return 6.0 * d[0]
        if k == "dot":
            return 2.0 * d[0]
        if k == "embed":
            return float(p["table"].shape[1])  # gather cost proxy
        if k == "onehot":
            return float(p["num"])
        if k == "forest":
            return float(p["feat"].shape[0] * p["depth"] * 4)
        if k == "slice":
            return float(p["stop"] - p["start"])
        if k == "const_vec":
            return 0.0
        raise ValueError(f"unknown atom kind {k}")

    def param_bytes(self) -> int:
        total = 0
        for v in self.params.values():
            if isinstance(v, (jnp.ndarray, np.ndarray)):
                total += int(np.prod(v.shape)) * v.dtype.itemsize
        return total

    # -- execution ---------------------------------------------------------
    def apply(self, *xs: jax.Array) -> jax.Array:
        k, p = self.kind, self.params
        if k == "matmul":
            x = xs[0] if xs[0].ndim == 2 else xs[0][:, None]
            return x @ jnp.asarray(p["w"])
        if k == "fused_dense":
            if self.backend == "pallas":
                from repro.kernels.fused_dense import ops as fd_ops
                return fd_ops.fused_dense(xs[0], jnp.asarray(p["w"]),
                                          jnp.asarray(p["b"]), p["act"])
            return _act(p["act"], xs[0] @ jnp.asarray(p["w"]) + jnp.asarray(p["b"]))
        if k == "bias":
            return xs[0] + jnp.asarray(p["b"])
        if k == "act":
            return _act(p["fn"], xs[0])
        if k == "concat":
            cols = [x if x.ndim == 2 else x[:, None].astype(jnp.float32) for x in xs]
            return jnp.concatenate(cols, axis=-1)
        if k == "cossim":
            a, b = xs
            num = jnp.sum(a * b, axis=-1)
            den = jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1) + 1e-8
            return num / den
        if k == "dot":
            return jnp.sum(xs[0] * xs[1], axis=-1)
        if k == "dist":
            return jnp.sqrt(jnp.sum(jnp.square(xs[0] - xs[1]), axis=-1) + 1e-12)
        if k == "embed":
            table = jnp.asarray(p["table"])
            ids = jnp.clip(xs[0].astype(jnp.int32), 0, table.shape[0] - 1)
            return table[ids]
        if k == "scale":
            return (xs[0] - jnp.asarray(p["mean"])) / (jnp.asarray(p["std"]) + 1e-8)
        if k == "onehot":
            return jax.nn.one_hot(xs[0].astype(jnp.int32), p["num"])
        if k == "binarize":
            return (xs[0] > p["threshold"]).astype(jnp.float32)
        if k == "forest":
            return _forest_apply(p, xs[0], self.backend)
        if k == "slice":
            return xs[0][:, p["start"]:p["stop"]]
        if k == "add":
            return xs[0] + xs[1]
        if k == "mul":
            return xs[0] * xs[1]
        if k == "sqrt":
            return jnp.sqrt(jnp.maximum(xs[0], 0.0))
        if k == "argmin":
            return jnp.argmin(xs[0], axis=-1).astype(jnp.float32)
        if k == "const_vec":
            v = jnp.asarray(p["value"])
            return jnp.broadcast_to(v, (xs[0].shape[0],) + v.shape)
        raise ValueError(f"unknown atom kind {k}")

    def apply_grid(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """A merge atom (``MERGES``) of every row of ``a`` [Na, d] with every
        row of ``b`` [Nb, d]: the [Na, Nb] grid of what ``apply`` gives the
        row pairs, as one product at float32 accuracy."""
        num = jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST)
        if self.kind == "dot":
            return num
        if self.kind == "cossim":
            den = jnp.outer(jnp.linalg.norm(a, axis=-1),
                            jnp.linalg.norm(b, axis=-1)) + 1e-8
            return num / den
        raise ValueError(f"{self.kind} is not a merge atom")


# atoms that merge two vectors into one number per row, and have a grid form
MERGES = ("cossim", "dot")


def _forest_apply(p: Dict, x: jax.Array, backend: str) -> jax.Array:
    """Array-form decision forest: complete binary trees of fixed depth.

    feat[T, 2^D-1] int32, thresh[T, 2^D-1] f32, leaf[T, 2^D] f32.
    Returns mean leaf value over trees (the ensemble vote).
    """
    if backend == "pallas":
        from repro.kernels.decision_forest import ops as df_ops
        return df_ops.forest_predict(x, jnp.asarray(p["feat"]),
                                     jnp.asarray(p["thresh"]),
                                     jnp.asarray(p["leaf"]))
    feat = jnp.asarray(p["feat"])
    thresh = jnp.asarray(p["thresh"])
    leaf = jnp.asarray(p["leaf"])
    depth = int(p["depth"])
    n, t = x.shape[0], feat.shape[0]
    node = jnp.zeros((n, t), dtype=jnp.int32)
    t_idx = jnp.arange(t)[None, :]
    for _ in range(depth):
        f = feat[t_idx, node]                          # [n, t]
        th = thresh[t_idx, node]
        xv = jnp.take_along_axis(x, f, axis=1)         # gather features
        node = 2 * node + 1 + (xv > th).astype(jnp.int32)
    leaf_idx = node - (2 ** depth - 1)
    lv = leaf[t_idx, leaf_idx]
    return jnp.mean(lv, axis=1)


# ---------------------------------------------------------------------------
# computation graph (bottom-level IR)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MLNode:
    id: int
    atom: Atom
    args: Tuple[Ref, ...]


@dataclasses.dataclass
class MLGraph:
    nodes: List[MLNode]  # topologically ordered
    out: int             # output node id
    n_inputs: int

    def node(self, nid: int) -> MLNode:
        for n in self.nodes:
            if n.id == nid:
                return n
        raise KeyError(nid)

    def apply(self, *inputs: jax.Array) -> jax.Array:
        return self.apply_ref(("node", self.out), dict(enumerate(inputs)))

    def apply_ref(self, ref: Ref, inputs: Mapping[int, jax.Array]) -> jax.Array:
        """The value at ``ref``, computed from the graph inputs it depends on
        (``inputs`` by index; the others may be absent), running only the
        nodes it needs."""
        if ref[0] == "in":
            return inputs[ref[1]]
        need = {ref[1]}
        for n in reversed(self.nodes):
            if n.id in need:
                need |= {r[1] for r in n.args if r[0] == "node"}
        vals: Dict[int, jax.Array] = {}
        for n in self.nodes:
            if n.id in need:
                xs = [inputs[r[1]] if r[0] == "in" else vals[r[1]]
                      for r in n.args]
                vals[n.id] = n.atom.apply(*xs)
        return vals[ref[1]]

    def infer_dims(self, in_dims: Sequence[int]) -> Dict[int, int]:
        dims: Dict[int, int] = {}
        for n in self.nodes:
            arg_dims = [in_dims[r[1]] if r[0] == "in" else dims[r[1]] for r in n.args]
            dims[n.id] = n.atom.out_dim(arg_dims)
        return dims

    def out_dim(self, in_dims: Sequence[int]) -> int:
        return self.infer_dims(in_dims)[self.out]

    def flops_per_row(self, in_dims: Sequence[int]) -> float:
        dims = self.infer_dims(in_dims)
        total = 0.0
        for n in self.nodes:
            arg_dims = [in_dims[r[1]] if r[0] == "in" else dims[r[1]] for r in n.args]
            total += n.atom.flops_per_row(arg_dims)
        return total

    def param_bytes(self) -> int:
        return sum(n.atom.param_bytes() for n in self.nodes)

    def input_deps(self) -> Dict[int, frozenset]:
        """node id -> set of graph-input indices it (transitively) depends on."""
        deps: Dict[int, frozenset] = {}
        for n in self.nodes:
            s = set()
            for r in n.args:
                if r[0] == "in":
                    s.add(r[1])
                else:
                    s |= deps[r[1]]
            deps[n.id] = frozenset(s)
        return deps

    def fresh_id(self) -> int:
        return max((n.id for n in self.nodes), default=-1) + 1


def chain(atoms: Sequence[Atom], n_inputs: int = 1) -> MLGraph:
    """Sequential graph: in0 -> a0 -> a1 -> ... (single input)."""
    nodes: List[MLNode] = []
    prev: Ref = ("in", 0)
    for i, a in enumerate(atoms):
        nodes.append(MLNode(id=i, atom=a, args=(prev,)))
        prev = ("node", i)
    return MLGraph(nodes=nodes, out=len(atoms) - 1, n_inputs=n_inputs)


# ---------------------------------------------------------------------------
# high-level ML function
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MLFunction:
    """A registered (possibly analyzable) ML function.

    ``graph`` is the bottom-level IR; ``opaque_fn`` is used instead when the
    model is a true black box (paper: huggingface/llm endpoints — here backed
    by local zoo models).
    """

    name: str
    graph: Optional[MLGraph] = None
    opaque_fn: Optional[Callable[..., jax.Array]] = None
    n_inputs: int = 1
    # optional hint for selectivity when used as a boolean filter
    selectivity_hint: Optional[float] = None

    def apply(self, *inputs: jax.Array) -> jax.Array:
        if self.graph is not None:
            return self.graph.apply(*inputs)
        assert self.opaque_fn is not None, f"{self.name} has no implementation"
        return self.opaque_fn(*inputs)

    def flops_per_row(self, in_dims: Sequence[int]) -> float:
        if self.graph is not None:
            return self.graph.flops_per_row(in_dims)
        return 1e6  # unknown black box: pessimistic constant

    def out_dim(self, in_dims: Sequence[int]) -> int:
        if self.graph is not None:
            return self.graph.out_dim(in_dims)
        return 0

    def param_bytes(self) -> int:
        return self.graph.param_bytes() if self.graph is not None else 0
