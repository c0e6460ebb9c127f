"""Decision-forest inference kernel — the R3-2 physical operator.

TPU adaptation: complete trees of fixed depth, traversed level by level with
no gather and no per-row branch.

Layout. Rows lie on sublanes and lanes: the caller passes the features
transposed and tiled, ``x[d, rows/L, L]``, so feature ``j`` of a block of
``8·L`` rows is one dense ``(8, L)`` tile, ``x_ref[j]``. Per-row state (the
path taken so far, the vote) is ``(8, L)`` tiles of the same rows. The tree
tables (feature id, threshold, leaf value; breadth-first, so each level's
nodes are contiguous) are flat int32/float32 arrays in SMEM, one stride of
``width`` entries per tree, and are read as scalars.

Traversal. At level ``l`` the kernel touches that level's ``2^l`` nodes and
no others. For node ``k`` it loads the feature row ``x_ref[feat[k]]`` (a
dynamic row address, no arithmetic, so the float32 test ``x[f] > th`` is
exact) and takes its threshold as a scalar. A binary tree of selects driven
by the decision bits of the levels above (the row's path) picks, for every
row, the feature value and threshold of the node the row sits at; one
compare then gives the level's bit. Levels wider than ``group`` nodes are
walked group by group in a loop: within a group the low path bits select,
across groups the row's node index within the level does. The last level
folds the leaf read into its node test: each node yields its left or right
leaf value, and the same selects pick the row's.

Grid: ``(row blocks, tree blocks)``. Each step holds one ``(d, 8, L)`` row
block in VMEM (fetched once per row block: its index does not change along
the tree axis) and ``tb`` trees' tables in SMEM, and loops over its trees,
each over the whole block. The vote accumulates in the resident output
block and is divided by the true tree count at the last tree block; padding
trees have zero leaves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _select(bits, entry, lo, size):
    """entry(lo + i) for each row, where i is the row's index among `size`
    consecutive entries, spelt by the last log2(size) path bits (most
    significant first): a binary tree of selects."""
    if size == 1:
        return entry(lo)
    half = size // 2
    b = bits[-half.bit_length()]
    take = lambda hi, lo_: jnp.where(b, hi, lo_)
    return jax.tree.map(take, _select(bits, entry, lo + half, half),
                        _select(bits, entry, lo, half))


def _level(bits, idx, entry, zero, n, group):
    """entry(k) for each row's node k among the level's n nodes; `zero` has
    entry's structure and the rows' shape."""
    if n <= group:
        return _select(bits, entry, 0, n)
    shift = group.bit_length() - 1
    hi = idx >> shift

    def step(q, acc):
        v = _select(bits, lambda r: entry(q * group + r), 0, group)
        return jax.tree.map(lambda a, b: jnp.where(hi == q, a, b), v, acc)

    return jax.lax.fori_loop(0, n // group, step, zero)


def _forest_kernel(feat_ref, thresh_ref, leaf_ref, x_ref, o_ref, *,
                   depth: int, width: int, tb: int, n_trees: int, group: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def tree(t, carry):
        base = t * width

        def node(k):              # feature row and threshold of node k
            return x_ref[feat_ref[base + k]], thresh_ref[base + k]

        zero = jnp.zeros(o_ref.shape, jnp.float32)
        bits = []
        idx = jnp.zeros(o_ref.shape, jnp.int32)     # node index in level
        for l in range(depth - 1):
            first = 2 ** l - 1
            xv, th = _level(bits, idx, lambda k: node(first + k),
                            (zero, zero), 2 ** l, group)
            bit = xv > th
            bits.append(bit)
            idx = 2 * idx + bit.astype(jnp.int32)
        first = 2 ** (depth - 1) - 1

        def leaf(k):              # node test folded into the leaf read
            xv, th = node(first + k)
            return jnp.where(xv > th, leaf_ref[base + 2 * k + 1],
                             leaf_ref[base + 2 * k])

        o_ref[...] += _level(bits, idx, leaf, zero, 2 ** (depth - 1), group)
        return carry

    jax.lax.fori_loop(0, tb, tree, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = o_ref[...] / n_trees


def forest_pallas(x: jax.Array, feat: jax.Array, thresh: jax.Array,
                  leaf: jax.Array, *, depth: int, width: int, tb: int,
                  n_trees: int, group: int,
                  interpret: bool = True) -> jax.Array:
    """x[d, rows/L, L] float32; feat/thresh/leaf flat, ``width`` entries per
    tree, a multiple of ``tb`` trees; n_trees is the true count. Returns the
    mean vote as ``[rows/L, L]``."""
    d, n_sub, n_lanes = x.shape
    assert n_sub % 8 == 0 and n_lanes % 128 == 0, "caller pads"
    tables = pl.BlockSpec((tb * width,), lambda i, j: (j,),
                          memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_forest_kernel, depth=depth, width=width, tb=tb,
                          n_trees=n_trees, group=group),
        grid=(n_sub // 8, feat.shape[0] // (tb * width)),
        in_specs=[tables, tables, tables,
                  pl.BlockSpec((d, 8, n_lanes), lambda i, j: (0, i, 0))],
        out_specs=pl.BlockSpec((8, n_lanes), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_sub, n_lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(feat, thresh, leaf, x)
