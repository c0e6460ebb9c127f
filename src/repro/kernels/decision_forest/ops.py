"""Public wrapper for the decision-forest kernel.

`forest_predict` lays the inputs out for the kernel and derives its schedule
from the shapes alone (rows, features, trees, depth), in `schedule`:

- features: ``x`` transposed to ``[d, rows]``, rows padded to whole blocks
  of ``8·L`` and tiled ``[d, rows/L, L]``, so each feature of a row block is
  one dense ``(8, L)`` tile. ``L`` is at most ``MAX_LANES`` and shrinks so
  that a double-buffered row block fits ``X_BLOCK_BYTES`` of VMEM; the row
  blocks are then balanced to waste the least padding.
- tree tables (`tables`): feature ids, thresholds and leaves, each tree's
  nodes in breadth-first order (level by level) at a stride of ``width``
  entries, a multiple of 128, flattened for SMEM. Trees are padded with
  zero-leaf trees to a whole number of tree blocks of ``tb`` trees, ``tb``
  as large as double-buffered tables in ``SMEM_TABLE_BYTES`` allow and then
  balanced; the kernel divides the vote by the true tree count, so the mean
  is unchanged.

On a TPU v5e much of a node's cost is fixed per select, whatever the width
of the rows it covers: ``(8, 1024)`` tiles ran the 100-tree depth-9 forest
over 289,000 rows 7.8x faster than ``(8, 128)`` tiles, and ``(8, 2048)``
tiles ran slower than ``(8, 1024)``. ``GROUP`` 32 ran 6% faster than 16
there, and 8 slower.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.decision_forest.kernel import forest_pallas

MAX_LANES = 1024               # lanes of a row block: 8 x 1024 rows
X_BLOCK_BYTES = 8 * 2 ** 20    # VMEM for the double-buffered row block
SMEM_TABLE_BYTES = 256 * 2 ** 10  # SMEM for the double-buffered tables
GROUP = 32                     # nodes a level selects among in one go


def schedule(n: int, d: int, n_trees: int, depth: int) -> dict:
    """Block shapes for n rows of d features and n_trees trees of `depth`."""
    width = 128 * common.cdiv(2 ** depth, 128)
    tb_max = max(1, SMEM_TABLE_BYTES // (2 * 3 * 4 * width))
    tb = common.cdiv(n_trees, common.cdiv(n_trees, tb_max))
    lanes_max = max(128, min(MAX_LANES,
                             X_BLOCK_BYTES // (2 * 4 * 8 * d) // 128 * 128))
    n_blocks = common.cdiv(n, 8 * lanes_max)
    lanes = 128 * common.cdiv(n, 8 * 128 * n_blocks)
    return dict(width=width, tb=tb, n_row_blocks=n_blocks, block_lanes=lanes)


def tables(feat: jax.Array, thresh: jax.Array, leaf: jax.Array, width: int,
           tb: int):
    """Flat SMEM tables: tree t's entries at [t*width, t*width + 2^depth),
    padded with zero-leaf trees to a multiple of tb trees."""
    n_trees = feat.shape[0]
    pad_t = (-n_trees) % tb

    def flat(a, dtype):
        a = jnp.pad(a.astype(dtype), ((0, pad_t), (0, width - a.shape[1])))
        return a.reshape(-1)

    return (flat(feat, jnp.int32), flat(thresh, jnp.float32),
            flat(leaf, jnp.float32))


@jax.jit
def forest_predict(x: jax.Array, feat: jax.Array, thresh: jax.Array,
                   leaf: jax.Array) -> jax.Array:
    n, d = x.shape
    n_trees, n_nodes = feat.shape
    depth = (n_nodes + 1).bit_length() - 1
    s = schedule(n, d, n_trees, depth)
    rows = s["n_row_blocks"] * 8 * s["block_lanes"]
    xt = common.pad_to(x.astype(jnp.float32).T, 1, rows)
    xt = xt.reshape(d, rows // s["block_lanes"], s["block_lanes"])
    out = forest_pallas(xt, *tables(feat, thresh, leaf, s["width"], s["tb"]),
                        depth=depth, width=s["width"], tb=s["tb"],
                        n_trees=n_trees, group=GROUP,
                        interpret=common.use_interpret())
    return out.reshape(-1)[:n].astype(x.dtype)
