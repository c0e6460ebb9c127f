"""Plain reference of the credit-card fraud query, and the comparison that
decides ``correct``.

Written from the query's meaning, in plain ``jax.numpy``; it uses nothing
of the program under test (``repro``). A tree sends a row right where its
feature is greater than the node's threshold; the forest's score is the
mean leaf over its trees. Answers are indexed by ``cc_id``.

Which rows pass the filters is computed for every row. The forest's score
is computed for ``score_rows`` rows drawn from the seed: each row's score
is an answer of its own, and the gathers of a whole table's traversal take
seconds on the chip, longer than the window.

``control=True`` computes the same in bfloat16 throughout, the next
precision below the float32 the configuration states: the control, which
the comparison has to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("amount_below", "time_above",
                                             "dtype"))
def _passes(table, *, amount_below, time_above, dtype):
    ok = ((table["amount"].astype(dtype) < amount_below)
          & (table["time"].astype(dtype) > time_above))
    return jnp.zeros(ok.shape, bool).at[table["cc_id"]].set(ok)


@functools.partial(jax.jit, static_argnames=("depth", "dtype"))
def _forest(x, forest, *, depth, dtype):
    x = x.astype(dtype)
    feat = forest["feat"]
    thresh = forest["thresh"].astype(dtype)
    leaf = forest["leaf"].astype(dtype)
    trees = jnp.arange(feat.shape[0])[None, :]
    node = jnp.zeros((x.shape[0], feat.shape[0]), jnp.int32)
    for _ in range(depth):
        value = jnp.take_along_axis(x, feat[trees, node], axis=1)
        node = 2 * node + 1 + (value > thresh[trees, node]).astype(jnp.int32)
    votes = leaf[trees, node - (2 ** depth - 1)]
    return jnp.sum(votes, axis=1, dtype=dtype) / feat.shape[0]


def reference(cfg: dict, raw: dict, forest, seed: int,
              control: bool = False) -> dict:
    """The answer of one payload, as numpy arrays indexed by cc_id: ``ok``
    for every row, ``fraud`` where ``scored``."""
    dtype = jnp.bfloat16 if control else jnp.float32
    t = raw["creditcard"]
    n = cfg["rows"]
    ok = np.asarray(_passes(t, amount_below=float(cfg["amount_below"]),
                            time_above=float(cfg["time_above"]), dtype=dtype))
    ids = np.sort(np.random.default_rng(seed).choice(
        n, size=min(cfg["score_rows"], n), replace=False))
    row_of = np.empty(n, np.int64)
    row_of[np.asarray(t["cc_id"])] = np.arange(n)
    score = _forest(t["cc_f"][jnp.asarray(row_of[ids])], forest,
                    depth=cfg["depth"], dtype=dtype)
    fraud = np.full(n, np.nan, np.float32)
    fraud[ids] = np.asarray(score.astype(jnp.float32))
    scored = np.zeros(n, bool)
    scored[ids] = True
    return {"ok": ok, "fraud": fraud, "scored": scored}


def live(cfg: dict, ref: dict) -> dict:
    return {"rows": int(ref["ok"].sum())}


def as_rows(cfg: dict, ref: dict) -> dict:
    """A reference's answer as the query's result rows (for the control)."""
    ids = np.flatnonzero(ref["ok"])
    return {"cc_id": ids.astype(np.int32), "fraud": ref["fraud"][ids]}


def compare(cfg: dict, got: dict, ref: dict) -> dict:
    """Numbers to hold to ``cfg["limits"]``. rows_off: cc_id rows missing,
    extra or repeated, over every row. fraud_err: the widest gap of a
    scored row's score."""
    n = cfg["rows"]
    ids = np.asarray(got["cc_id"]).astype(np.int64)
    inside = (ids >= 0) & (ids < n)
    ids = np.where(inside, ids, 0)
    seen = np.bincount(ids[inside], minlength=n)
    ok = inside & ref["ok"][ids]
    rows_off = (int((~ok).sum()) + int((seen > 1).sum())
                + int((ref["ok"] & (seen == 0)).sum()))
    checked = ok & ref["scored"][ids]
    if not checked.any():
        return {"rows_off": rows_off, "fraud_err": float("inf")}
    err = np.abs(np.asarray(got["fraud"], np.float64)[checked]
                 - ref["fraud"][ids[checked]])
    return {"rows_off": rows_off, "fraud_err": float(err.max())}
