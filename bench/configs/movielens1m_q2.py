"""MovieLens-1M recommendation Q2 of the CactusDB paper.

Movies kept by a trending DNN join their 4,096-d tag vectors; the result is
cross joined with every user; a user-interest DNN over concat(user_f,
movie_f) keeps a share of the pairs; an autoencoder compresses the tag
vector to ``dense_rep``, and a DLRM scores each kept pair from
``dense_rep``, a user embedding and a movie embedding. Sizes are in
``movielens1m_q2.json``; the plain reference is in
``movielens1m_q2_reference.py``.

Every seed does the same work, on the same compiled programs. The model
(every network, the movie features the trending DNN reads and the user
features) is fixed, as a deployment's is: it is drawn from a key that does
not depend on the seed. The two output biases are set at the stated
precision so that exactly ``trending_movies`` movies and
``interest_pairs`` of the users x trending movies pairs pass, each
threshold midway between two neighbouring logits. The seed deals the
feature rows to user and movie ids and draws the tag vectors, and so
decides every answer.

Payload ``i`` of a run rolls the feature columns (``user_f``; ``movie_f``
with ``mt_relevance``) by ``i`` rows against the id columns, so each
payload has answers of its own, while the multiset of (user, movie)
feature pairs, and with it the count that passes each filter, stays.
"""
from __future__ import annotations

import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ir
from repro.mlfuncs.functions import Atom, MLFunction, MLGraph, MLNode
from repro.mlfuncs.registry import Registry
from repro.relational.table import Table

FIXED_KEY = 20260311  # the model and the fixed features, never the seed
PAIR_BLOCK = 64       # users per block of the pair logits


def _rec_helpers():
    """``movielens1m_rec.py``, loaded beside this file for its helpers."""
    path = Path(__file__).with_name("movielens1m_rec.py")
    spec = importlib.util.spec_from_file_location("movielens1m_q2_rec", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_rec = _rec_helpers()
prng, _dense = _rec.prng, _rec._dense


def _apply(layers, x, last, inputs):
    """The network at the precision the configuration states: each
    product's inputs rounded to ``inputs``, accumulated in float32; a
    layer of width 1, which XLA computes as a float32 multiply and sum,
    unrounded."""
    def rounded(a):
        return a.astype(inputs).astype(jnp.float32)
    for n, (w, b) in enumerate(layers):
        if w.shape[1] > 1:
            x, w = rounded(x), rounded(w)
        x = jnp.dot(x, w, precision="highest",
                    preferred_element_type=jnp.float32) + b
        if n < len(layers) - 1:
            x = jax.nn.relu(x)
    return last(x)


def _logit(p: float) -> float:
    return float(np.log(p / (1 - p)))


def _kth_largest(z, k: int):
    """The ``k``-th largest of float32 ``z`` (any shape): a bisection over
    the floats' order-preserving uint32 images, 32 counting passes. A sort
    of the 11.7M pair logits takes a minute to compile for the TPU."""
    u = jax.lax.bitcast_convert_type(z.astype(jnp.float32), jnp.uint32)
    top = jnp.uint32(0x80000000)
    key = jnp.where(u >= top, ~u, u | top)

    def halve(_, bounds):  # the answer lies in [lo, hi]
        lo, hi = bounds
        mid = lo + (hi - lo) // 2 + ((hi - lo) & 1)
        ok = jnp.sum(key >= mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = jax.lax.fori_loop(0, 32, halve,
                              (jnp.uint32(0), jnp.uint32(0xFFFFFFFF)))
    return jax.lax.bitcast_convert_type(
        jnp.where(lo >= top, lo & ~top, ~lo), jnp.float32)


def _pair_logits(layers, user_f, movie_f, inputs, block=PAIR_BLOCK):
    """The interest DNN's logit of every (user, movie) row pair, [users,
    movies], over concat(user_f, movie_f) at the stated precision; in
    blocks of users, so that a block's hidden layer is all that is held."""
    n_u, n_m = user_f.shape[0], movie_f.shape[0]
    pad = -n_u % block
    blocks = jnp.pad(user_f, ((0, pad), (0, 0))).reshape(
        -1, block, user_f.shape[1])

    def one(u):
        x = jnp.concatenate([
            jnp.broadcast_to(u[:, None], (block, n_m, u.shape[1])),
            jnp.broadcast_to(movie_f[None], (block,) + movie_f.shape)], -1)
        z = _apply(layers, x.reshape(block * n_m, -1), lambda y: y[:, 0],
                   inputs)
        return z.reshape(block, n_m)

    return jax.lax.map(one, blocks).reshape(-1, n_m)[:n_u]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _generate(key, fixed_key, frozen_cfg, n_payloads):
    # the fixed key is an argument, not a constant: XLA would otherwise
    # fold the fixed model into the program while compiling it
    c = dict(frozen_cfg)
    n_u, n_m = c["users"], c["movies"]
    inputs = jnp.dtype(c["matmul_inputs"])
    ks = jax.random.split(fixed_key, 9)
    weights = {name: _dense(k, c[dims]) for k, (name, dims) in zip(ks, (
        ("trending", "trending_dnn"), ("interest", "interest_dnn"),
        ("autoencoder", "autoencoder"), ("user_emb", "user_emb"),
        ("movie_emb", "movie_emb"), ("dlrm_bottom", "dlrm_bottom"),
        ("dlrm_top", "dlrm_top")))}
    movie_f = 0.5 * jax.random.normal(ks[7], (n_m, c["movie_feature_dim"]))
    user_f = 0.5 * jax.random.normal(ks[8], (n_u, c["user_feature_dim"]))

    def set_bias(layers, z, k, threshold):
        # the threshold falls midway between the k-th and the next logit
        shift = _logit(threshold) - 0.5 * (_kth_largest(z, k)
                                           + _kth_largest(z, k + 1))
        w, b = layers[-1]
        return layers[:-1] + [(w, b + shift)], z + shift

    z = _apply(weights["trending"], movie_f, lambda x: x[:, 0], inputs)
    weights["trending"], z = set_bias(weights["trending"], z,
                                      c["trending_movies"],
                                      c["trending_threshold"])
    trending = jnp.argsort(-z)[:c["trending_movies"]]
    # kept [users, movies]: flattening it takes XLA half a minute to compile
    zi = _pair_logits(weights["interest"], user_f, movie_f[trending], inputs)
    weights["interest"], _ = set_bias(weights["interest"], zi,
                                      c["interest_pairs"],
                                      c["interest_threshold"])

    k_u, k_m, k_t, k_mask, k_g, k_y, k_a, k_o, k_s = jax.random.split(key, 9)
    user_f = user_f[jax.random.permutation(k_u, n_u)]
    movie_f = movie_f[jax.random.permutation(k_m, n_m)]
    tags = jax.random.normal(k_t, (n_m, c["tag_dim"])) * (
        jax.random.uniform(k_mask, (n_m, c["tag_dim"])) < c["tag_density"])
    fixed = {
        "users": {"user_id": jnp.arange(n_u, dtype=jnp.int32),
                  "gender": (jax.random.uniform(k_s, (n_u,)) < 0.72)
                  .astype(jnp.int32),
                  "age": jax.random.choice(k_a, jnp.asarray(
                      [1., 18., 25., 35., 45., 50., 56.]), (n_u,)),
                  "occupation": jax.random.randint(k_o, (n_u,), 0, 21)},
        "movies": {"movie_id": jnp.arange(n_m, dtype=jnp.int32),
                   "genre": jax.random.permutation(
                       k_g, jnp.arange(n_m, dtype=jnp.int32) % c["genres"]),
                   "year": jnp.floor(1919 + 82 * jax.random.uniform(
                       k_y, (n_m,)))},
        "movie_tags": {"mt_movie_id": jnp.arange(n_m, dtype=jnp.int32)},
    }

    def payload(i):
        roll = functools.partial(jnp.roll, shift=i, axis=0)
        tables = {
            "users": {**fixed["users"], "user_f": roll(user_f)},
            "movies": {**fixed["movies"], "movie_f": roll(movie_f)},
            "movie_tags": {**fixed["movie_tags"], "mt_relevance": roll(tags)},
        }
        valid = {t: jnp.ones((n,), bool) for t, n in (
            ("users", n_u), ("movies", n_m), ("movie_tags", n_m))}
        return tables, valid

    stacked = jax.vmap(payload)(jnp.arange(n_payloads))
    # one output per payload: slicing them apart outside this program
    # would compile a small program per column and payload in every run
    per_payload = [jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
                   for i in range(n_payloads)]
    return per_payload, weights


def generate(cfg: dict, seed: int, n_payloads: int):
    """``n_payloads`` payloads, each ``(tables, valid masks)``, and the
    model weights, made on the device in one call from ``seed``."""
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                          for k, v in cfg.items()
                          if isinstance(v, (int, float, str, list))))
    return _generate(prng(seed), jax.random.key(FIXED_KEY), frozen,
                     n_payloads)


# ---------------------------------------------------------------------------
# the query, as the program's IR (a copy of rec_q2, so that later edits to
# repro.data.workloads do not move this benchmark)
# ---------------------------------------------------------------------------

def _layers(layers, acts, prev, nid, nodes):
    for (w, b), act in zip(layers, acts):
        for atom in (Atom("matmul", {"w": np.asarray(w, np.float32)}),
                     Atom("bias", {"b": np.asarray(b, np.float32)}),
                     Atom("act", {"fn": act})):
            nodes.append(MLNode(id=nid, atom=atom, args=(prev,)))
            prev, nid = ("node", nid), nid + 1
    return prev, nid


def _acts(layers, last):
    return ["relu"] * (len(layers) - 1) + [last]


def _mlp(name, layers, last):
    nodes = []
    out, _ = _layers(layers, _acts(layers, last), ("in", 0), 0, nodes)
    return MLFunction(name=name, graph=MLGraph(nodes=nodes, out=out[1],
                                               n_inputs=1), n_inputs=1)


def _concat_mlp(name, layers, last, n_inputs):
    nodes = [MLNode(id=0, atom=Atom("concat"),
                    args=tuple(("in", k) for k in range(n_inputs)))]
    out, _ = _layers(layers, _acts(layers, last), ("node", 0), 1, nodes)
    return MLFunction(name=name, graph=MLGraph(nodes=nodes, out=out[1],
                                               n_inputs=n_inputs),
                      n_inputs=n_inputs)


def _dlrm(name, bottom, top):
    """top(concat(bottom(in0), in1, in2)), the repository's DLRM."""
    nodes = []
    bot, nid = _layers(bottom, _acts(bottom, "relu"), ("in", 0), 0, nodes)
    nodes.append(MLNode(id=nid, atom=Atom("concat"),
                        args=(bot, ("in", 1), ("in", 2))))
    out, _ = _layers(top, _acts(top, "sigmoid"), ("node", nid), nid + 1,
                     nodes)
    return MLFunction(name=name, graph=MLGraph(nodes=nodes, out=out[1],
                                               n_inputs=3), n_inputs=3)


def query(cfg: dict, weights) -> ir.Plan:
    reg = Registry()
    trend = reg.register(_mlp("trending_movie_dnn", weights["trending"],
                              "sigmoid"))
    # exact share of all movies above the threshold, as rec_q2 measures it
    trend.selectivity_hint = min(
        1.0, cfg["trending_movies"] / cfg["movies"] + 1e-6)
    reg.register(_concat_mlp("user_interest_dnn", weights["interest"],
                             "sigmoid", 2))
    reg.register(_mlp("autoencoder", weights["autoencoder"], "identity"))
    reg.register(_dlrm("dlrm", weights["dlrm_bottom"], weights["dlrm_top"]))
    reg.register(_mlp("user_emb", weights["user_emb"], "identity"))
    reg.register(_mlp("movie_emb", weights["movie_emb"], "identity"))
    movie_side = ir.Join(
        ir.Filter(ir.Scan("movies"),
                  pred=ir.Cmp(">", ir.Call("trending_movie_dnn",
                                           (ir.Col("movie_f"),)),
                              ir.Const(float(cfg["trending_threshold"]))),
                  selectivity=trend.selectivity_hint),
        ir.Scan("movie_tags"), "movie_id", "mt_movie_id")
    pairs = ir.Filter(
        ir.CrossJoin(ir.Scan("users"), movie_side),
        pred=ir.Cmp(">", ir.Call("user_interest_dnn",
                                 (ir.Col("user_f"), ir.Col("movie_f"))),
                    ir.Const(float(cfg["interest_threshold"]))),
        selectivity=cfg["interest_pairs"] / (cfg["users"]
                                             * cfg["trending_movies"]))
    q = ir.Project(
        pairs,
        outputs=(("dense_rep", ir.Call("autoencoder",
                                       (ir.Col("mt_relevance"),))),),
        keep=("user_id", "movie_id", "user_f", "movie_f"))
    q = ir.Project(
        q,
        outputs=(("rec_score", ir.Call("dlrm", (
            ir.Col("dense_rep"),
            ir.Call("user_emb", (ir.Col("user_f"),)),
            ir.Call("movie_emb", (ir.Col("movie_f"),))))),),
        keep=("user_id", "movie_id"))
    return ir.Plan(q, reg)


def build(cfg: dict, seed: int, n_payloads: int) -> dict:
    """Everything a run needs: the query, its catalog (payload 0), every
    payload as the program's tables and as plain arrays for the reference,
    and the weights. Fails at once, before any table is made, on a program
    without the budget counters (``repro.tracing.count``) that
    ``work`` logs: such a program predates the separation of the calls
    above a cross join, and would plan the autoencoder over every pair."""
    from repro import tracing
    if not hasattr(tracing, "count"):
        raise RuntimeError("this program has no repro.tracing.count: it "
                           "cannot plan this query to fit one chip")
    generated, weights = generate(cfg, seed, n_payloads)
    raw = [tables for tables, _ in generated]
    payloads = [{t: Table.from_columns(cols, valid=valid[t])
                 for t, cols in tables.items()}
                for tables, valid in generated]
    catalog = ir.Catalog()
    for name in ("users", "movies", "movie_tags"):
        catalog.add(name, payloads[0][name])
    return {"plan": query(cfg, weights), "catalog": catalog,
            "payloads": payloads, "raw": raw, "weights": weights}


def _dense_flops(dims) -> int:
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def work(cfg: dict, live: dict) -> dict:
    """Operations of one query, from the logical query and its live rows:
    the least any correct plan does. Each dense layer is 2*in*out per row
    it varies over: a network of one side's columns once per live row of
    that side (the trending DNN over every movie; the autoencoder, the
    movie embedding and the DLRM's bottom over trending movies; the user
    embedding over users); a first layer over a concat of both sides as
    R2-1 splits it, its user part per user and its movie part per trending
    movie; every later layer of a pair once per pair (the interest DNN's
    over all pairs, the DLRM top's over kept pairs). Also logs the
    process's counters (fallbacks, budget prunings) and the compiled plan's
    device bytes beside the planner's analytic peak."""
    _log_executables()
    u, m = live["users"], live["trending_movies"]
    du, dm = cfg["user_feature_dim"], cfg["movie_feature_dim"]
    hidden = cfg["interest_dnn"][1]
    top_in, top_hidden = cfg["dlrm_top"][0], cfg["dlrm_top"][1]
    user_part = cfg["user_emb"][-1]
    flops = (_dense_flops(cfg["trending_dnn"]) * live["movies"]
             + (_dense_flops(cfg["autoencoder"])
                + _dense_flops(cfg["movie_emb"])
                + _dense_flops(cfg["dlrm_bottom"])) * m
             + _dense_flops(cfg["user_emb"]) * u
             + 2 * du * hidden * u + 2 * dm * hidden * m
             + 2 * user_part * top_hidden * u
             + 2 * (top_in - user_part) * top_hidden * m
             + _dense_flops(cfg["interest_dnn"][1:]) * live["pairs"]
             + _dense_flops(cfg["dlrm_top"][1:]) * live["kept_pairs"])
    return {"model_flops": float(flops)}


def _log_executables() -> None:
    from repro import tracing
    print(f"counts: {dict(tracing.counts)}", file=sys.stderr, flush=True)
    for e in tracing.executables():
        print(f"executable {e.module} (batch {e.batch_size}): compiled "
              f"temporaries {e.temp_bytes}, arguments {e.argument_bytes}, "
              f"outputs {e.output_bytes} bytes; analytic peak "
              f"{e.analytic_peak_bytes:.0f} bytes", file=sys.stderr,
              flush=True)
