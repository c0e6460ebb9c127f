"""MovieLens-1M recommendation, the CactusDB paper's recommendation Q1.

Per-user mean rating over the ratings table, joined to users; movies
filtered by genre IN (1, 4, 7) and by a trending DNN; the two sides cross
joined and scored by a two-tower cosine model. Sizes are in
``movielens1m_rec.json``; the plain reference is in
``movielens1m_rec_reference.py``.

Every seed does the same work, on the same compiled programs. The model
(the towers, the trending DNN and the movie features it reads) is fixed,
as a deployment's is: it is drawn from a key that does not depend on the
seed, and the program compiles its weights into its executables, so a
model that changed with the seed would recompile every run. The counts
that set the work are fixed multisets which the seed only deals out:
ratings per user, the rating values, genres per movie, and the number of
movies that pass each filter (the trending DNN's output bias is set so
that exactly ``trending_movies`` movies score above the threshold, and
genres are dealt so that ``trending_genre_movies`` of those pass the genre
filter too). The seed draws the user features, deals the ratings to
users and the genres to movies, and so decides every answer.

Payload ``i`` of a run rolls the users and movies by ``i`` rows, draws new
user features, and deals the ratings anew (the same multisets), so each
payload has answers of its own while the filters keep their counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ir
from repro.mlfuncs.functions import Atom, MLFunction, MLGraph, MLNode
from repro.mlfuncs.registry import Registry
from repro.relational.table import Table

FIXED_KEY = 20260218  # the model and the fixed multisets, never the seed


def prng(seed: int):
    """A key for any non-negative seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _dense(key, dims):
    """He-scaled weights and small biases, the layers of one network."""
    out = []
    for k, (i, o) in zip(jax.random.split(key, len(dims) - 1),
                         zip(dims[:-1], dims[1:])):
        kw, kb = jax.random.split(k)
        out.append((jax.random.normal(kw, (i, o)) / np.sqrt(i),
                    0.1 * jax.random.normal(kb, (o,))))
    return out


def _apply(layers, x, last, inputs):
    """The network at the precision the configuration states: each
    product's inputs rounded to ``inputs``, accumulated in float32."""
    def rounded(a):
        return a.astype(inputs).astype(jnp.float32)
    for n, (w, b) in enumerate(layers):
        x = jnp.dot(rounded(x), rounded(w), precision="highest",
                    preferred_element_type=jnp.float32) + b
        if n < len(layers) - 1:
            x = jax.nn.relu(x)
    return last(x)


def _fixed_columns(key, c):
    """Multisets that set the work: the same for every seed."""
    k_u, k_r, k_m, k_y, k_a, k_o, k_g = jax.random.split(key, 7)
    n_u, n_r, n_m = c["users"], c["ratings"], c["movies"]
    floor = c["min_ratings_per_user"]
    # every user rates `floor` times; the rest go to users in lognormal
    # shares (MovieLens 1M: median 96, mean 166, most 2,314 per user)
    share = jnp.exp(0.9 * jax.random.normal(k_u, (n_u,)))
    extra = jnp.floor(share / share.sum() * (n_r - floor * n_u))
    extra = extra.astype(jnp.int32).at[0].add(
        n_r - floor * n_u - extra.sum().astype(jnp.int32))
    rank = jnp.repeat(jnp.arange(n_u), floor + extra, total_repeat_length=n_r)
    shares = np.asarray(c["rating_shares"], np.float64)
    counts = np.floor(shares * n_r).astype(np.int64)
    counts[-1] += n_r - counts.sum()
    # ratings 1..5 in those counts, made on the device, not a constant
    rating = 1 + jnp.searchsorted(jnp.asarray(np.cumsum(counts)),
                                  jnp.arange(n_r), side="right")
    movie_rank = jnp.floor(n_m * jax.random.uniform(k_r, (n_r,)) ** 2)
    return {
        "rank": rank, "rating": rating.astype(jnp.float32),
        "movie_rank": movie_rank.astype(jnp.int32),
        "year": jnp.floor(1919 + 82 * jax.random.uniform(k_y, (n_m,))),
        "age": jax.random.choice(
            k_a, jnp.asarray([1., 18., 25., 35., 45., 50., 56.]), (n_u,)),
        "occupation": jax.random.randint(k_o, (n_u,), 0, 21),
        "gender": (jax.random.uniform(k_g, (n_u,)) < 0.72).astype(jnp.int32),
    }


def _deal_genres(key, order, c):
    """Genres in equal shares, dealt so that exactly
    ``trending_genre_movies`` of the top ``trending_movies`` (by the
    trending DNN, ``order``) carry a genre the query keeps."""
    n_m, n_g = c["movies"], c["genres"]
    keep = set(c["genre_filter"])
    all_g = np.arange(n_m) % n_g
    kept = all_g[np.isin(all_g, list(keep))]
    other = all_g[~np.isin(all_g, list(keep))]
    k_top, k_both = c["trending_movies"], c["trending_genre_movies"]
    top = np.concatenate([kept[:k_both], other[:k_top - k_both]])
    rest = np.concatenate([kept[k_both:], other[k_top - k_both:]])
    k1, k2 = jax.random.split(key)
    by_rank = jnp.concatenate([jax.random.permutation(k1, jnp.asarray(top)),
                               jax.random.permutation(k2, jnp.asarray(rest))])
    return jnp.zeros((n_m,), jnp.int32).at[order].set(by_rank)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _generate(key, fixed_key, frozen_cfg, n_payloads):
    # the fixed key is an argument, not a constant: XLA would otherwise
    # fold the fixed multisets into the program while compiling it
    c = dict(frozen_cfg)
    n_u, n_r, n_m = c["users"], c["ratings"], c["movies"]
    inputs = jnp.dtype(c["matmul_inputs"])
    fx = _fixed_columns(fixed_key, c)
    k_w, k_t, k_mf = jax.random.split(jax.random.fold_in(fixed_key, 1), 3)
    k_g, k_uid, k_mid, k_pay = jax.random.split(key, 4)
    kw1, kw2 = jax.random.split(k_w)
    weights = {"user_tower": _dense(kw1, c["user_tower"]),
               "movie_tower": _dense(kw2, c["movie_tower"]),
               "trending": _dense(k_t, c["trending_dnn"])}
    movie_f = 0.5 * jax.random.normal(k_mf, (n_m, c["movie_feature_dim"]))
    # output bias: the threshold falls midway between the trending_movies-th
    # and the next logit, at the stated precision, so that exactly that
    # many movies score above it and none lies nearer to it than half the
    # gap between those two logits
    z = _apply(weights["trending"], movie_f, lambda x: x[:, 0], inputs)
    order = jnp.argsort(-z)
    k = c["trending_movies"]
    logit_t = np.log(c["trending_threshold"] / (1 - c["trending_threshold"]))
    shift = logit_t - 0.5 * (z[order[k - 1]] + z[order[k]])
    w_last, b_last = weights["trending"][-1]
    weights["trending"][-1] = (w_last, b_last + shift)
    genre = _deal_genres(k_g, order, c)
    user_of_rank = jax.random.permutation(k_uid, n_u)
    movie_of_rank = jax.random.permutation(k_mid, n_m)
    r_user = user_of_rank[fx["rank"]].astype(jnp.int32)
    r_movie = movie_of_rank[fx["movie_rank"]].astype(jnp.int32)

    def payload(i):
        kp = jax.random.fold_in(k_pay, i)
        k_f, k1, k2 = jax.random.split(kp, 3)
        p_rows = jax.random.permutation(k1, n_r)
        p_rating = jax.random.permutation(k2, n_r)
        roll = functools.partial(jnp.roll, shift=i, axis=0)
        tables = {
            "users": {
                "user_id": roll(jnp.arange(n_u, dtype=jnp.int32)),
                "gender": roll(fx["gender"]),
                "age": roll(fx["age"]).astype(jnp.float32),
                "occupation": roll(fx["occupation"]).astype(jnp.int32),
                "user_f": 0.5 * jax.random.normal(
                    k_f, (n_u, c["user_feature_dim"])),
            },
            "movies": {
                "movie_id": roll(jnp.arange(n_m, dtype=jnp.int32)),
                "genre": roll(genre),
                "year": roll(fx["year"]).astype(jnp.float32),
                "movie_f": roll(movie_f),
            },
            "ratings": {
                "r_user_id": r_user[p_rows],
                "r_movie_id": r_movie[p_rows],
                "rating": fx["rating"][p_rating],
            },
        }
        valid = {t: jnp.ones((n,), bool)
                 for t, n in (("users", n_u), ("movies", n_m),
                              ("ratings", n_r))}
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
# the query, as the program's IR (a copy of rec_q1, so that later edits to
# repro.data.workloads do not move this benchmark)
# ---------------------------------------------------------------------------

def _tower_nodes(layers, start, src, nodes):
    prev, nid = src, start
    for n, (w, b) in enumerate(layers):
        act = "relu" if n < len(layers) - 1 else "identity"
        for atom in (Atom("matmul", {"w": np.asarray(w, np.float32)}),
                     Atom("bias", {"b": np.asarray(b, np.float32)}),
                     Atom("act", {"fn": act})):
            nodes.append(MLNode(id=nid, atom=atom, args=(prev,)))
            prev, nid = ("node", nid), nid + 1
    return prev, nid


def _two_tower(name, user_layers, movie_layers):
    nodes = []
    u, nid = _tower_nodes(user_layers, 0, ("in", 0), nodes)
    m, nid = _tower_nodes(movie_layers, nid, ("in", 1), nodes)
    nodes.append(MLNode(id=nid, atom=Atom("cossim"), args=(u, m)))
    return MLFunction(name=name, graph=MLGraph(nodes=nodes, out=nid,
                                               n_inputs=2), n_inputs=2)


def _ffnn(name, layers):
    nodes, prev, nid = [], ("in", 0), 0
    for n, (w, b) in enumerate(layers):
        act = "relu" if n < len(layers) - 1 else "sigmoid"
        for atom in (Atom("matmul", {"w": np.asarray(w, np.float32)}),
                     Atom("bias", {"b": np.asarray(b, np.float32)}),
                     Atom("act", {"fn": act})):
            nodes.append(MLNode(id=nid, atom=atom, args=(prev,)))
            prev, nid = ("node", nid), nid + 1
    return MLFunction(name=name, graph=MLGraph(nodes=nodes, out=nid - 1,
                                               n_inputs=1), n_inputs=1)


def query(cfg: dict, weights, catalog: ir.Catalog) -> ir.Plan:
    reg = Registry()
    reg.register(_two_tower("two_tower", weights["user_tower"],
                            weights["movie_tower"]))
    trend = reg.register(_ffnn("trending_movie_dnn", weights["trending"]))
    # exact share of all movies above the threshold, as rec_q1 measures it
    trend.selectivity_hint = min(
        1.0, cfg["trending_movies"] / cfg["movies"] + 1e-6)
    movie_side = ir.Filter(
        ir.Filter(ir.Scan("movies"),
                  pred=ir.IsIn(ir.Col("genre"), tuple(cfg["genre_filter"]))),
        pred=ir.Cmp(">", ir.Call("trending_movie_dnn", (ir.Col("movie_f"),)),
                    ir.Const(float(cfg["trending_threshold"]))),
        selectivity=trend.selectivity_hint)
    user_agg = ir.Aggregate(ir.Scan("ratings"), key="r_user_id",
                            aggs=(("user_avg_rating", ("mean", "rating")),),
                            num_groups=catalog.stats["users"].capacity)
    user_side = ir.Join(ir.Scan("users"), user_agg, "user_id", "r_user_id")
    q = ir.Project(
        ir.CrossJoin(user_side, movie_side),
        outputs=(("score", ir.Call("two_tower", (ir.Col("user_f"),
                                                 ir.Col("movie_f")))),),
        keep=("user_id", "movie_id", "user_avg_rating"))
    return ir.Plan(q, reg)


def build(cfg: dict, seed: int, n_payloads: int) -> dict:
    """Everything a run needs: the query, its catalog (payload 0), every
    payload as the program's tables and as plain arrays for the reference,
    and the weights."""
    generated, weights = generate(cfg, seed, n_payloads)
    raw = [tables for tables, _ in generated]
    payloads = [{t: Table.from_columns(cols, valid=valid[t])
                 for t, cols in tables.items()}
                for tables, valid in generated]
    catalog = ir.Catalog()
    for name in ("users", "movies", "ratings"):
        catalog.add(name, payloads[0][name])
    return {"plan": query(cfg, weights, catalog), "catalog": catalog,
            "payloads": payloads, "raw": raw, "weights": weights}


def work(cfg: dict, live: dict) -> dict:
    """Operations of one query, from the logical query and its live rows:
    each dense layer 2*in*out per row it is applied to; each tower once per
    live row of its own side; the cosine per output pair (three 128-d
    reductions, 6*d, and not the realization the plan chose)."""
    def dense(dims):
        return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    d = cfg["user_tower"][-1]
    flops = (dense(cfg["trending_dnn"]) * live["genre_movies"]
             + dense(cfg["user_tower"]) * live["users"]
             + dense(cfg["movie_tower"]) * live["movies"]
             + 6 * d * live["users"] * live["movies"])
    return {"model_flops": float(flops)}
