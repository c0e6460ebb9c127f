"""Decision-forest inference kernel — the R3-2 physical operator.

TPU adaptation: tree traversal is branch- and gather-free. For a block of
rows and one tree:
  1. feature gather  x[feat[j]]  →  xv = x @ onehot(feat)ᵀ  (MXU matmul at
     HIGHEST precision, so exact, with a precomputed one-hot matrix, done
     once per tree, host-side in ops.py)
  2. decision bits   D = xv > thresh                (VPU compare, all nodes)
  3. traversal       node ← 2·node+1+D[node]; the D[node] gather is a
     one-hot select: sum((node == iota) · D)        (VPU, no gather op)
  4. leaf read       pred = onehot(leaf_idx) · leaf (VPU select)
Votes accumulate across the tree grid dimension in VMEM scratch.

Grid: (N/bm, T). Row block bm×d plus the per-tree one-hot (d×nodes) and
decision matrices (bm×nodes) bound the VMEM working set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _forest_kernel(x_ref, fonehot_ref, thresh_ref, leaf_ref, o_ref, acc_ref,
                   *, depth: int, n_trees: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                        # [bm, d]
    fo = fonehot_ref[0]                   # [d, nodes]
    th = thresh_ref[0]                    # [1, nodes] -> broadcast
    lv = leaf_ref[0]                      # [1, leaves]
    n_nodes = fo.shape[1]
    # the one-hot matmul is a gather and must be exact: at default precision
    # the MXU rounds x to bf16 and flips decisions near the thresholds
    xv = jnp.dot(x, fo, preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)  # [bm, nodes]
    dec = (xv > th).astype(jnp.float32)   # [bm, nodes]
    bm = x.shape[0]
    node = jnp.zeros((bm,), jnp.int32)
    iota_nodes = jax.lax.broadcasted_iota(jnp.int32, (1, n_nodes), 1)
    for _ in range(depth):
        sel = (node[:, None] == iota_nodes).astype(jnp.float32)  # [bm, nodes]
        bit = jnp.sum(sel * dec, axis=1).astype(jnp.int32)
        node = 2 * node + 1 + bit
    leaf_idx = node - (n_nodes)           # complete tree: nodes = 2^depth - 1
    n_leaves = lv.shape[1]
    iota_leaves = jax.lax.broadcasted_iota(jnp.int32, (1, n_leaves), 1)
    lsel = (leaf_idx[:, None] == iota_leaves).astype(jnp.float32)
    pred = jnp.sum(lsel * lv, axis=1)     # [bm]
    acc_ref[...] += pred[:, None]

    @pl.when(t == n_trees - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / n_trees).astype(o_ref.dtype)


def forest_pallas(x: jax.Array, fonehot: jax.Array, thresh: jax.Array,
                  leaf: jax.Array, depth: int, *, bm: int = 128,
                  interpret: bool = True) -> jax.Array:
    n, d = x.shape
    n_trees, _, n_nodes = fonehot.shape
    assert n % bm == 0, "caller pads"
    grid = (n // bm, n_trees)
    out = pl.pallas_call(
        functools.partial(_forest_kernel, depth=depth, n_trees=n_trees),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, t: (i, 0)),
            pl.BlockSpec((1, d, n_nodes), lambda i, t: (t, 0, 0)),
            pl.BlockSpec((1, 1, n_nodes), lambda i, t: (t, 0, 0)),
            pl.BlockSpec((1, 1, leaf.shape[2]), lambda i, t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(x, fonehot, thresh, leaf)
    return out[:, 0]
