"""Plain reference of the MovieLens-1M recommendation Q2, and the
comparison that decides ``correct``.

Written from the query's meaning, in plain ``jax.numpy`` at the highest
matmul precision; it uses nothing of the program under test (``repro``).
Every answer is indexed by id: per movie, its trending logit; per (user,
movie), the interest logit and the DLRM score. The networks of one side
(the autoencoder, the embeddings, the DLRM's bottom) run once per row of
that side, as their meaning allows; the interest DNN and the DLRM's top
run on every pair's concatenated inputs, in blocks of users, so that a
block's hidden layer is all that is held.

It computes at the precision the configuration states
(``matmul_inputs``): the inputs of each dense layer's matrix product are
rounded to that type, as the TPU's default precision rounds them to
bfloat16 for one pass, and everything else is float32. A layer of width 1
(the last of the trending, interest and DLRM top networks) is a float32
product: XLA computes a matrix-vector product as a multiply and a sum on
the vector unit, not on the matrix unit, and rounds nothing.
``control=True`` computes the same in bfloat16 throughout, the next
precision down: the control, which the comparison has to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

USER_BLOCK = 64


def _mlp(layers, x, dtype, inputs, relu_last=False):
    def rounded(a):
        return a.astype(inputs).astype(dtype)
    for n, (w, b) in enumerate(layers):
        if w.shape[1] > 1:
            x, w = rounded(x), rounded(w)
        x = jnp.dot(x, w.astype(dtype), preferred_element_type=dtype)
        x = x + b.astype(dtype)
        if n < len(layers) - 1 or relu_last:
            x = jnp.maximum(x, 0)
    return x


def _by_id(ids, rows, n):
    return jnp.zeros((n,) + rows.shape[1:], rows.dtype).at[ids].set(rows)


@functools.partial(jax.jit, static_argnames=("n_users", "n_movies", "dtype",
                                             "inputs"))
def _reference(raw, weights, *, n_users, n_movies, dtype, inputs):
    u, m, t = raw["users"], raw["movies"], raw["movie_tags"]
    with jax.default_matmul_precision("highest"):
        mlp = functools.partial(_mlp, dtype=dtype, inputs=inputs)
        user_f = _by_id(u["user_id"], u["user_f"].astype(dtype), n_users)
        movie_f = _by_id(m["movie_id"], m["movie_f"].astype(dtype), n_movies)
        tags = _by_id(t["mt_movie_id"], t["mt_relevance"].astype(dtype),
                      n_movies)
        trend = mlp(weights["trending"], movie_f)[:, 0]
        dense_rep = mlp(weights["autoencoder"], tags)
        bottom = mlp(weights["dlrm_bottom"], dense_rep, relu_last=True)
        memb = mlp(weights["movie_emb"], movie_f)
        uemb = mlp(weights["user_emb"], user_f)
        pad = -n_users % USER_BLOCK

        def blocks(x):
            return jnp.pad(x, ((0, pad), (0, 0))).reshape(
                -1, USER_BLOCK, x.shape[1])

        def pairs(a, b):  # every (user of the block, movie) row, concat
            return jnp.concatenate([
                jnp.broadcast_to(a[:, None], (USER_BLOCK, n_movies,
                                              a.shape[1])),
                jnp.broadcast_to(b[None], (USER_BLOCK,) + b.shape)],
                -1).reshape(USER_BLOCK * n_movies, -1)

        def one(args):
            uf, ue = args
            interest = mlp(weights["interest"], pairs(uf, movie_f))[:, 0]
            top_in = jnp.concatenate([
                jnp.broadcast_to(bottom[None], (USER_BLOCK,) + bottom.shape)
                .reshape(USER_BLOCK * n_movies, -1), pairs(ue, memb)], -1)
            score = jax.nn.sigmoid(mlp(weights["dlrm_top"], top_in)[:, 0])
            return (interest.reshape(USER_BLOCK, n_movies),
                    score.reshape(USER_BLOCK, n_movies))

        interest, score = jax.lax.map(one, (blocks(user_f), blocks(uemb)))
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return {"trend": f32(trend),
            "interest": f32(interest.reshape(-1, n_movies)[:n_users]),
            "score": f32(score.reshape(-1, n_movies)[:n_users])}


def reference(cfg: dict, raw: dict, weights, seed: int,
              control: bool = False) -> dict:
    """The answer of one payload, as numpy arrays indexed by id: logits
    per movie (``trend``) and per (user, movie) (``interest``), and the
    score per (user, movie). Every answer is computed, so ``seed`` draws
    nothing here."""
    dtype = jnp.bfloat16 if control else jnp.float32
    inputs = dtype if control else jnp.dtype(cfg["matmul_inputs"])
    out = _reference(raw, weights, n_users=cfg["users"],
                     n_movies=cfg["movies"], dtype=dtype, inputs=inputs)
    return {k: np.asarray(v) for k, v in out.items()}


def _gaps(cfg: dict, ref: dict):
    """Signed logit distance from each filter's threshold: per movie
    (trending) and per (user, movie) (interest)."""
    def t(p):
        return np.log(p / (1 - p))
    return (ref["trend"].astype(np.float64) - t(cfg["trending_threshold"]),
            ref["interest"].astype(np.float64) - t(cfg["interest_threshold"]))


def _kept(cfg: dict, ref: dict) -> np.ndarray:
    trend, interest = _gaps(cfg, ref)
    return (trend > 0)[None, :] & (interest > 0)


def live(cfg: dict, ref: dict) -> dict:
    """Live rows of each side and of the pairs, for the work counts."""
    trend, _ = _gaps(cfg, ref)
    n_trend = int((trend > 0).sum())
    return {"users": cfg["users"], "movies": cfg["movies"],
            "trending_movies": n_trend,
            "pairs": cfg["users"] * n_trend,
            "kept_pairs": int(_kept(cfg, ref).sum())}


def as_rows(cfg: dict, ref: dict) -> dict:
    """A reference's answer as the query's result rows (for the control)."""
    uu, mm = np.nonzero(_kept(cfg, ref))
    return {"user_id": uu.astype(np.int32), "movie_id": mm.astype(np.int32),
            "rec_score": ref["score"][uu, mm]}


def describe(cfg: dict, ref: dict) -> dict:
    """Readings of a payload that say how hard its row set is to get
    right: the nearest a trending movie's logit and a trending pair's
    interest logit come to their thresholds, and how many pairs lie within
    the ``flip_logit`` limit of it."""
    trend, interest = _gaps(cfg, ref)
    pair = np.abs(interest[:, trend > 0])
    return {"trending_margin": float(np.abs(trend).min()),
            "interest_margin": float(pair.min()),
            "pairs_within_limit": int(
                (pair <= cfg["limits"]["flip_logit"]).sum())}


def compare(cfg: dict, got: dict, ref: dict) -> dict:
    """Numbers to hold to ``cfg["limits"]``.

    flip_logit: the largest distance, in logit, from its threshold of a
    movie (trending) or a trending pair (interest) that the program put on
    the other side of it than the reference (0 where none). A row that
    near the threshold may go either way at the stated precision.
    rows_off: (user, movie) rows missing, extra or repeated: every pair of
    a trending movie whose interest logit lies above the threshold, and no
    other, where a movie or pair within the ``flip_logit`` limit of its
    threshold may go either way. score_err: the widest gap of a row's
    rec_score."""
    n_u, n_m = cfg["users"], cfg["movies"]
    limit = cfg["limits"]["flip_logit"]
    trend, interest = _gaps(cfg, ref)
    u = np.asarray(got["user_id"]).astype(np.int64)
    m = np.asarray(got["movie_id"]).astype(np.int64)
    inside = (u >= 0) & (u < n_u) & (m >= 0) & (m < n_m)
    u, m = np.where(inside, u, 0), np.where(inside, m, 0)
    seen = np.bincount((u * n_m + m)[inside],
                       minlength=n_u * n_m).reshape(n_u, n_m)
    present = seen > 0
    trend_ok, interest_ok = trend > 0, interest > 0
    # the movies the program kept: those with a pair present, and a
    # trending movie none of whose pairs passes, which is absent either way
    chosen = present.any(axis=0) | (trend_ok & ~interest_ok.any(axis=0))
    movie_flip = chosen != trend_ok
    must = np.where(movie_flip & (np.abs(trend) <= limit), chosen, trend_ok)
    expected = must[None, :] & interest_ok
    wrong = present != expected
    flips = np.concatenate([np.abs(trend[movie_flip]),
                            np.abs(interest[must[None, :] & wrong])])
    flip_logit = float(flips.max()) if flips.size else 0.0
    off = wrong & ~(must[None, :] & (np.abs(interest) <= limit))
    rows_off = int(off.sum()) + int((seen > 1).sum()) + int((~inside).sum())
    ok = inside & expected[u, m]
    if not ok.any():
        return {"rows_off": rows_off, "flip_logit": flip_logit,
                "score_err": float("inf")}
    score_err = np.abs(np.asarray(got["rec_score"], np.float64)[ok]
                       - ref["score"][u[ok], m[ok]])
    return {"rows_off": rows_off, "flip_logit": flip_logit,
            "score_err": float(score_err.max())}
