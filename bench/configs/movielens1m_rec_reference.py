"""Plain reference of the MovieLens-1M recommendation query, and the
comparison that decides ``correct``.

Written from the query's meaning, in plain ``jax.numpy`` at the highest
matmul precision; it uses nothing of the program under test (``repro``).
Every answer is indexed by id: per user, whether it joins and its mean
rating; per movie, whether it passes the genre filter and its trending
probability; per (user, movie), the two-tower cosine.

It computes at the precision the configuration states
(``matmul_inputs``): the inputs of each dense layer's matrix product are
rounded to that type, as the TPU's default precision rounds them to
bfloat16 for one pass, and everything else is float32. ``control=True``
computes the same in bfloat16 throughout, the next precision down: the
control, which the comparison has to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _mlp(layers, x, dtype, inputs, last):
    def rounded(a):
        return a.astype(inputs).astype(dtype)
    for n, (w, b) in enumerate(layers):
        x = jnp.dot(rounded(x), rounded(w), preferred_element_type=dtype)
        x = x + b.astype(dtype)
        if n < len(layers) - 1:
            x = jnp.maximum(x, 0)
    return last(x)


@functools.partial(jax.jit, static_argnames=("n_users", "n_movies", "genres",
                                             "dtype", "inputs"))
def _reference(raw, weights, *, n_users, n_movies, genres, dtype, inputs):
    u, m, r = raw["users"], raw["movies"], raw["ratings"]
    with jax.default_matmul_precision("highest"):
        # mean rating per user id, over that user's ratings
        total = jnp.zeros((n_users,), dtype).at[r["r_user_id"]].add(
            r["rating"].astype(dtype))
        count = jnp.zeros((n_users,), dtype).at[r["r_user_id"]].add(1)
        avg = total / jnp.maximum(count, 1)
        listed = jnp.zeros((n_users,), bool).at[u["user_id"]].set(True)
        user_ok = listed & (count > 0)
        # movie filters
        genre_ok = jnp.zeros((n_movies,), bool).at[m["movie_id"]].set(
            jnp.isin(m["genre"], jnp.asarray(genres)))
        p_rows = _mlp(weights["trending"], m["movie_f"].astype(dtype), dtype,
                      inputs, lambda x: jax.nn.sigmoid(x[:, 0]))
        p = jnp.zeros((n_movies,), dtype).at[m["movie_id"]].set(p_rows)
        # two towers, once per row of each side, and the cosine per pair
        ut = _mlp(weights["user_tower"], u["user_f"].astype(dtype), dtype,
                  inputs, lambda x: x)
        mt = _mlp(weights["movie_tower"], m["movie_f"].astype(dtype), dtype,
                  inputs, lambda x: x)
        ut = jnp.zeros((n_users, ut.shape[1]), dtype).at[u["user_id"]].set(ut)
        mt = jnp.zeros((n_movies, mt.shape[1]), dtype).at[m["movie_id"]].set(mt)
        dot = jnp.dot(ut, mt.T, preferred_element_type=dtype)
        norm = (jnp.sqrt(jnp.sum(ut * ut, axis=1))[:, None]
                * jnp.sqrt(jnp.sum(mt * mt, axis=1))[None, :])
        score = dot / (norm + 1e-8)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return {"user_ok": user_ok, "avg": f32(avg), "genre_ok": genre_ok,
            "p": f32(p), "score": f32(score)}


def reference(cfg: dict, raw: dict, weights, seed: int,
              control: bool = False) -> dict:
    """The answer of one payload, as numpy arrays indexed by id. Every
    answer is computed, so ``seed`` draws nothing here."""
    dtype = jnp.bfloat16 if control else jnp.float32
    inputs = dtype if control else jnp.dtype(cfg["matmul_inputs"])
    out = _reference(raw, weights, n_users=cfg["users"],
                     n_movies=cfg["movies"],
                     genres=tuple(cfg["genre_filter"]), dtype=dtype,
                     inputs=inputs)
    return {k: np.asarray(v) for k, v in out.items()}


def live(cfg: dict, ref: dict) -> dict:
    """Live rows of each side, for the work counts."""
    kept = ref["genre_ok"] & (ref["p"] > cfg["trending_threshold"])
    return {"users": int(ref["user_ok"].sum()),
            "genre_movies": int(ref["genre_ok"].sum()),
            "movies": int(kept.sum())}


def as_rows(cfg: dict, ref: dict) -> dict:
    """A reference's answer as the query's result rows (for the control)."""
    kept = ref["genre_ok"] & (ref["p"] > cfg["trending_threshold"])
    uu, mm = np.meshgrid(np.flatnonzero(ref["user_ok"]), np.flatnonzero(kept),
                         indexing="ij")
    uu, mm = uu.ravel(), mm.ravel()
    return {"user_id": uu.astype(np.int32), "movie_id": mm.astype(np.int32),
            "user_avg_rating": ref["avg"][uu], "score": ref["score"][uu, mm]}


def _logit_gap(cfg: dict, p) -> np.ndarray:
    """How far each trending probability lies from the threshold, in
    logit (where the DNN computes it)."""
    p = np.clip(np.asarray(p, np.float64), 1e-12, 1 - 1e-12)
    t = cfg["trending_threshold"]
    return np.abs(np.log(p / (1 - p)) - np.log(t / (1 - t)))


def describe(cfg: dict, ref: dict) -> dict:
    """Readings of a payload that say how hard its row set is to get
    right: the nearest that a kept-genre movie's trending logit comes to
    the threshold's, and how many lie within the ``flip_logit`` limit,
    1e-3 and 1e-2 of it."""
    gap = _logit_gap(cfg, ref["p"][ref["genre_ok"]])
    if not gap.size:
        return {}
    return {"trending_margin": float(gap.min()),
            "within_limit": int((gap <= cfg["limits"]["flip_logit"]).sum()),
            "within_1e-3": int((gap < 1e-3).sum()),
            "within_1e-2": int((gap < 1e-2).sum())}


def compare(cfg: dict, got: dict, ref: dict) -> dict:
    """Numbers to hold to ``cfg["limits"]``.

    flip_logit: the largest distance, in logit, from the threshold of a
    kept-genre movie that the program put on the other side of it than
    the reference (0 where none). A movie that near the threshold may go
    either way at the stated precision: a float32 difference of one unit
    in a hidden activation can move its bfloat16 rounding, and the logit
    with it. rows_off: (user, movie) rows missing, extra or repeated:
    every movie of a kept genre that the reference keeps (or, within the
    ``flip_logit`` limit of the threshold, that the program keeps),
    crossed with every user who has ratings, and no other row. avg_err:
    the widest gap of a row's user_avg_rating. score_err: the widest gap
    of a row's score."""
    n_u, n_m = cfg["users"], cfg["movies"]
    kept = ref["genre_ok"] & (ref["p"] > cfg["trending_threshold"])
    u = np.asarray(got["user_id"]).astype(np.int64)
    m = np.asarray(got["movie_id"]).astype(np.int64)
    inside = (u >= 0) & (u < n_u) & (m >= 0) & (m < n_m)
    u, m = np.where(inside, u, 0), np.where(inside, m, 0)
    flat = u * n_m + m
    seen = np.bincount(flat[inside], minlength=n_u * n_m)
    chosen = np.zeros(n_m, bool)
    chosen[m[inside]] = True
    flipped = ref["genre_ok"] & (chosen != kept)
    gap = _logit_gap(cfg, ref["p"])
    flip_logit = float(gap[flipped].max()) if flipped.any() else 0.0
    must = np.where(flipped & (gap <= cfg["limits"]["flip_logit"]),
                    chosen, kept)
    ok = inside & ref["user_ok"][u] & must[m]
    extra = int((~ok).sum())
    repeated = int((seen > 1).sum())
    expected = int(ref["user_ok"].sum()) * int(must.sum())
    present = int(((seen > 0).reshape(n_u, n_m)[ref["user_ok"]][:, must]).sum())
    rows_off = extra + repeated + (expected - present)
    if not ok.any():
        return {"rows_off": rows_off, "flip_logit": flip_logit,
                "avg_err": float("inf"), "score_err": float("inf")}
    avg_err = np.abs(np.asarray(got["user_avg_rating"], np.float64)[ok]
                     - ref["avg"][u[ok]])
    score_err = np.abs(np.asarray(got["score"], np.float64)[ok]
                       - ref["score"][u[ok], m[ok]])
    return {"rows_off": rows_off, "flip_logit": flip_logit,
            "avg_err": float(avg_err.max()),
            "score_err": float(score_err.max())}
