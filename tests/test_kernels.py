"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(0)


def _arr(shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("m,k,n", [(7, 12, 5), (130, 200, 70), (256, 512, 128),
                                   (1, 128, 128)])
@pytest.mark.parametrize("act", ["identity", "relu", "sigmoid", "gelu",
                                 "squared_relu"])
def test_fused_dense(m, k, n, act):
    from repro.kernels.fused_dense import ops, ref
    x, w, b = _arr((m, k)), _arr((k, n)), _arr((n,))
    np.testing.assert_allclose(ops.fused_dense(x, w, b, act),
                               ref.fused_dense(x, w, b, act),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_dense_dtypes(dtype):
    from repro.kernels.fused_dense import ops, ref
    x, w, b = _arr((64, 96), dtype), _arr((96, 32), dtype), _arr((32,), dtype)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(ops.fused_dense(x, w, b, "relu"), np.float32),
        np.asarray(ref.fused_dense(x, w, b, "relu"), np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("m,k,n,t", [(10, 16, 40, 4), (130, 300, 520, 8),
                                     (64, 512, 1024, 16)])
def test_block_matmul(m, k, n, t):
    from repro.kernels.block_matmul import ops, ref
    x, w = _arr((m, k)), _arr((k, n))
    np.testing.assert_allclose(ops.block_matmul(x, w, t),
                               ref.block_matmul(x, w, t),
                               rtol=1e-4, atol=1e-4)


# each case moves one shape edge of the kernel's schedule: depth (1 and 2
# nodes a level, levels walked group by group at depth 9), trees (1, a
# prime, several tree blocks, padding trees), rows (one row, lane padding,
# several row blocks), features (1 and wide)
@pytest.mark.parametrize("n,d,t,depth", [
    (20, 8, 4, 3), (150, 16, 10, 5), (64, 29, 25, 6),
    (50, 8, 4, 1), (50, 8, 4, 2), (300, 8, 5, 6), (1000, 29, 100, 9),
    (200, 8, 1, 5), (200, 8, 7, 5), (200, 8, 100, 5), (200, 32, 160, 6),
    (200, 8, 43, 9),
    (1, 8, 4, 4), (127, 8, 4, 4), (129, 8, 4, 4), (1000, 8, 4, 4),
    (2000, 8, 3, 4), (9000, 3, 5, 4),
    (300, 1, 5, 5), (300, 29, 5, 5), (300, 40, 5, 5)])
def test_decision_forest(n, d, t, depth):
    from repro.kernels.decision_forest import ops, ref
    x = _arr((n, d))
    nn = 2 ** depth - 1
    feat = jnp.asarray(rng.integers(0, d, (t, nn)), jnp.int32)
    th = _arr((t, nn))
    leaf = _arr((t, 2 ** depth))
    np.testing.assert_allclose(ops.forest_predict(x, feat, th, leaf),
                               ref.forest_predict(x, feat, th, leaf),
                               rtol=1e-4, atol=1e-4)


def _forest_with_ties(n, d, t, depth, seed):
    """Distinct leaves, and thresholds that equal some rows' feature values
    exactly: a misrouted row, or a tie sent right, changes the vote."""
    r = np.random.default_rng(seed)
    x = r.standard_normal((n, d)).astype(np.float32)
    nn = 2 ** depth - 1
    feat = r.integers(0, d, (t, nn)).astype(np.int32)
    th = r.standard_normal((t, nn)).astype(np.float32)
    tie = r.random((t, nn)) < 0.5
    th[tie] = x[r.integers(0, n, tie.sum()), feat[tie]]
    leaf = np.arange(t * 2 ** depth, dtype=np.float32).reshape(t, -1)
    return tuple(jnp.asarray(a) for a in (x, feat, th, leaf))


def test_decision_forest_routes_every_row_exactly():
    from repro.kernels.decision_forest import ops, ref
    x, feat, th, leaf = _forest_with_ties(600, 7, 9, 8, seed=11)
    np.testing.assert_array_equal(ops.forest_predict(x, feat, th, leaf),
                                  ref.forest_predict(x, feat, th, leaf))


def test_forest_tables_reproduce_the_trees():
    """The SMEM tables ops.py builds (breadth-first nodes at a per-tree
    stride, zero-leaf padding trees) are the same forest: the oracle run on
    them, scaled back to the true tree count, gives the original vote."""
    from repro.kernels.decision_forest import ops, ref
    n, d, t, depth = 300, 8, 43, 9
    x, feat, th, leaf = _forest_with_ties(n, d, t, depth, seed=12)
    s = ops.schedule(n, d, t, depth)
    assert t % s["tb"], "the case must need padding trees"
    f_t, th_t, leaf_t = ops.tables(feat, th, leaf, s["width"], s["tb"])
    padded = f_t.shape[0] // s["width"]
    assert padded % s["tb"] == 0 and padded > t
    back = lambda a, k: a.reshape(padded, s["width"])[:, :k]
    nn = 2 ** depth - 1
    vote = ref.forest_predict(x, back(f_t, nn), back(th_t, nn),
                              back(leaf_t, nn + 1)) * padded / t
    np.testing.assert_allclose(vote, ref.forest_predict(x, feat, th, leaf),
                               rtol=1e-6)


def test_forest_matches_mlfuncs_atom():
    """Kernel path (R4-2 backend='pallas') == jnp atom path."""
    from repro.mlfuncs import builders
    fn = builders.decision_forest("f", 8, 4, 12, seed=3)
    atom = fn.graph.nodes[0].atom
    x = _arr((40, 12))
    y_jnp = atom.apply(x)
    import dataclasses
    atom_p = dataclasses.replace(atom, backend="pallas")
    y_pl = atom_p.apply(x)
    np.testing.assert_allclose(y_jnp, y_pl, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,hq,hkv,s,d", [(2, 4, 2, 37, 16), (1, 8, 8, 256, 64),
                                          (2, 6, 3, 100, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(b, hq, hkv, s, d, causal):
    from repro.kernels.flash_attention import ops, ref
    q, k, v = _arr((b, hq, s, d)), _arr((b, hkv, s, d)), _arr((b, hkv, s, d))
    got = ops.flash_attention(q, k, v, causal)
    kk = jnp.repeat(k, hq // hkv, 1).reshape(b * hq, s, d)
    vv = jnp.repeat(v, hq // hkv, 1).reshape(b * hq, s, d)
    want = ref.attention(q.reshape(b * hq, s, d), kk, vv, causal)
    np.testing.assert_allclose(got.reshape(b * hq, s, d), want,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bh,g,d,s", [(4, 6, 32, 300), (2, 8, 64, 1024),
                                      (1, 1, 16, 50)])
def test_flash_decode(bh, g, d, s):
    from repro.kernels.flash_decode import ops, ref
    q, k, v = _arr((bh, g, d)), _arr((bh, s, d)), _arr((bh, s, d))
    np.testing.assert_allclose(ops.decode_attention(q, k, v),
                               ref.decode_attention(q, k, v),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_shard_merge():
    """Partial (acc, m, l) merged across cache shards == full softmax —
    the correctness basis of the S-sharded decode (O3 on the KV cache)."""
    from repro.kernels.flash_decode import ops, ref
    bh, g, d, s = 3, 4, 32, 384
    q, k, v = _arr((bh, g, d)), _arr((bh, s, d)), _arr((bh, s, d))
    want = ref.decode_attention(q, k, v)
    splits = [(0, 128), (128, 256), (256, 384)]
    accs, ms, ls = [], [], []
    for lo, hi in splits:
        a, m, l = ops.decode_partials(q, k[:, lo:hi], v[:, lo:hi])
        accs.append(a)
        ms.append(m)
        ls.append(l)
    merged = ref.merge_partials(accs, ms, ls)
    np.testing.assert_allclose(merged, want, rtol=2e-4, atol=2e-4)


def test_fused_dense_atom_backend_swap():
    """R4-2's physical replacement: jnp vs pallas fused_dense atoms agree."""
    import dataclasses
    from repro.mlfuncs.functions import Atom
    w, b = _arr((24, 48)), _arr((48,))
    a_jnp = Atom("fused_dense", {"w": w, "b": b, "act": "relu"})
    a_pl = dataclasses.replace(a_jnp, backend="pallas")
    x = _arr((20, 24))
    np.testing.assert_allclose(a_jnp.apply(x), a_pl.apply(x),
                               rtol=1e-4, atol=1e-4)
