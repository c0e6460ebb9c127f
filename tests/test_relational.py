"""Relational engine vs numpy oracle — unit + hypothesis property tests."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests degrade to skips
from hypothesis import given, settings, strategies as st

from repro.relational import ops, oracle
from repro.relational.table import Table


def mk_table(rng, n, with_vec=True):
    cols = {
        "id": jnp.arange(n, dtype=jnp.int32),
        "k": jnp.asarray(rng.integers(0, max(n // 3, 2), n), jnp.int32),
        "x": jnp.asarray(rng.random(n) * 10, jnp.float32),
    }
    if with_vec:
        cols["v"] = jnp.asarray(rng.standard_normal((n, 5)), jnp.float32)
    return Table.from_columns(cols)


def assert_tables_equal(t: Table, o, atol=1e-5):
    a = t.canonical()
    b = oracle.canonical(o)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-4, atol=atol, err_msg=k)


def test_filter_and_compact():
    rng = np.random.default_rng(0)
    t = mk_table(rng, 50)
    mask = t["x"] > 5.0
    ft = ops.filter_(t, mask)
    npo = oracle.filter_(t.to_numpy(), np.asarray(mask))
    assert_tables_equal(ft, npo)
    ct = ops.compact(ft, 32)
    assert ct.capacity == 32
    assert_tables_equal(ct, npo)


def test_compact_up():
    rng = np.random.default_rng(1)
    t = mk_table(rng, 10)
    ct = ops.compact(t, 16)
    assert ct.capacity == 16
    assert_tables_equal(ct, t.to_numpy())


def test_fk_join():
    rng = np.random.default_rng(2)
    left = Table.from_columns({
        "fk": jnp.asarray(rng.integers(0, 12, 40), jnp.int32),
        "a": jnp.asarray(rng.random(40), jnp.float32)})
    right = Table.from_columns({
        "rid": jnp.arange(8, dtype=jnp.int32),
        "b": jnp.asarray(rng.random(8), jnp.float32)})
    j = ops.fk_join(left, right, "fk", "rid")
    npo = oracle.fk_join(left.to_numpy(), right.to_numpy(), "fk", "rid")
    assert_tables_equal(j, npo)


def test_fk_join_respects_invalid_right_rows():
    left = Table.from_columns({"fk": jnp.asarray([0, 1, 2], jnp.int32)})
    right = Table.from_columns({"rid": jnp.asarray([0, 1, 2], jnp.int32),
                                "b": jnp.asarray([1., 2., 3.], jnp.float32)},
                               valid=jnp.asarray([True, False, True]))
    j = ops.fk_join(left, right, "fk", "rid")
    out = j.canonical()
    np.testing.assert_array_equal(out["fk"], [0, 2])


def test_cross_join():
    rng = np.random.default_rng(3)
    a, b = mk_table(rng, 6, False), mk_table(rng, 4, False)
    b = b.rename({"id": "id2", "k": "k2", "x": "x2"})
    x = ops.cross_join(a, b)
    npo = oracle.cross_join(a.to_numpy(), b.to_numpy())
    assert_tables_equal(x, npo)


def test_aggregate():
    rng = np.random.default_rng(4)
    t = mk_table(rng, 60)
    g = ops.aggregate(t, "k", {"s": ("sum", "x"), "m": ("mean", "x"),
                               "c": ("count", "x"), "mx": ("max", "x"),
                               "mn": ("min", "x"), "vs": ("mean", "v")},
                      num_groups=64)
    npo = oracle.aggregate(t.to_numpy(), "k",
                           {"s": ("sum", "x"), "m": ("mean", "x"),
                            "c": ("count", "x"), "mx": ("max", "x"),
                            "mn": ("min", "x"), "vs": ("mean", "v")})
    assert_tables_equal(g, npo, atol=1e-4)


def test_aggregate_masked_rows_excluded():
    t = Table.from_columns({"k": jnp.asarray([0, 0, 1], jnp.int32),
                            "x": jnp.asarray([1., 100., 2.], jnp.float32)},
                           valid=jnp.asarray([True, False, True]))
    g = ops.aggregate(t, "k", {"s": ("sum", "x")}, num_groups=4)
    out = g.canonical()
    np.testing.assert_allclose(out["s"], [1.0, 2.0])


_AGGS = {"s": ("sum", "x"), "m": ("mean", "x"), "c": ("count", "x"),
         "mn": ("min", "x"), "mx": ("max", "x"), "vm": ("mean", "v")}
_SENT = np.iinfo(np.int32).max
_KEY_POOLS = {
    "small": np.arange(12),
    "wide": np.array([-2**31, -10**9, -7, 0, 3, 10**9, 2**31 - 2]),
}


def _agg_case(rng, n, pool, p_valid):
    keys = rng.choice(_KEY_POOLS[pool], n).astype(np.int32)
    x = rng.integers(-50, 50, n).astype(np.float32) / 4
    v = rng.standard_normal((n, 3)).astype(np.float32)
    valid = rng.random(n) < p_valid
    return keys, x, v, valid


def _check_aggregate(out, keys, x, v, valid, num_groups):
    """``out`` (numpy columns + valid) against the oracle over valid rows.

    The smallest ``num_groups`` keys come first in ascending order; the
    remaining slots hold the sentinel key, zero sums and counts, and invalid.
    """
    ref = oracle.aggregate({"k": keys[valid], "x": x[valid], "v": v[valid]},
                           "k", _AGGS)
    g = min(len(ref["k"]), num_groups)
    np.testing.assert_array_equal(out["valid"], np.arange(num_groups) < g)
    np.testing.assert_array_equal(out["k"][:g], ref["k"][:g])
    np.testing.assert_array_equal(out["k"][g:], _SENT)
    for name in _AGGS:
        want = np.reshape(ref[name][:g], out[name][:g].shape)  # empty: (0,)
        np.testing.assert_allclose(out[name][:g], want, rtol=1e-5, atol=1e-5,
                                   err_msg=name)
    for name, empty in (("s", 0.0), ("m", 0.0), ("c", 0.0), ("mn", np.inf),
                        ("mx", -np.inf), ("vm", 0.0)):
        np.testing.assert_array_equal(out[name][g:], empty, err_msg=name)


@functools.partial(jax.jit, static_argnums=4)
def _aggregate_columns(keys, x, v, valid, num_groups):
    t = Table(columns={"k": keys, "x": x, "v": v}, valid=valid)
    g = ops.aggregate(t, "k", _AGGS, num_groups)
    return dict(g.columns, valid=g.valid)


@pytest.mark.parametrize("n,pool,p_valid,num_groups", [
    (60, "small", 1.0, 16),   # every kind, all rows valid
    (60, "small", 0.6, 16),   # invalid rows excluded
    (40, "wide", 0.8, 8),     # negative and very large keys
    (80, "small", 0.9, 5),    # more distinct keys than slots: smallest kept
    (7, "small", 1.0, 20),    # more slots than rows
    (9, "small", 0.5, 20),    # more slots than rows, some rows invalid
    (30, "wide", 0.0, 6),     # no valid row
], ids=["kinds", "invalid", "wide_keys", "overflow", "slots_gt_rows",
        "slots_gt_rows_invalid", "all_invalid"])
@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap3"])
def test_aggregate_matches_oracle_cases(n, pool, p_valid, num_groups,
                                        batched):
    rng = np.random.default_rng(n * 7 + num_groups)
    if not batched:
        case = _agg_case(rng, n, pool, p_valid)
        out = _aggregate_columns(*map(jnp.asarray, case), num_groups)
        _check_aggregate(jax.tree.map(np.asarray, out), *case, num_groups)
        return
    cases = [_agg_case(rng, n, pool, p_valid) for _ in range(3)]
    stacked = [jnp.asarray(np.stack(col)) for col in zip(*cases)]
    out = jax.vmap(lambda *a: _aggregate_columns(*a, num_groups))(*stacked)
    for i, case in enumerate(cases):
        _check_aggregate({k: np.asarray(c[i]) for k, c in out.items()},
                         *case, num_groups)


def test_aggregate_sum_precision_is_per_group():
    # a group of values near 1.0 sorted after groups near 1e6: its sum's
    # error follows its own magnitude, not the running total before it
    rng = np.random.default_rng(11)
    big = 1e6 + rng.random(3000) * 1000
    small = 1.0 + rng.random(500) * 1e-3
    keys = np.concatenate([np.repeat([0, 1, 2], 1000), np.full(500, 9)])
    x = np.concatenate([big, small]).astype(np.float32)
    order = rng.permutation(len(x))
    t = Table.from_columns({"k": jnp.asarray(keys[order], jnp.int32),
                            "x": jnp.asarray(x[order])})
    g = ops.aggregate(t, "k", {"s": ("sum", "x")}, num_groups=4)
    want = x[keys == 9].astype(np.float64).sum()
    got = float(np.asarray(g["s"])[3])
    assert abs(got - want) <= 8 * np.finfo(np.float32).eps * want, (got, want)


def _hlo_ops(compiled):
    """Counts of sorts and scatters, and the elements each gather reads."""
    txt = compiled.as_text()
    count = {op: len(re.findall(rf"\s{op}\(", txt)) for op in ("sort", "scatter")}
    gathers = [int(np.prod([int(d) for d in dims.split(",") if d]))
               for dims in re.findall(r"=\s+\w+\[([0-9,]*)\]\S*\s+gather\(", txt)]
    return count, gathers


def test_aggregate_compiles_to_one_sort_and_no_scatter():
    n, num_groups = 4096, 64
    aggs = {k: a for k, a in _AGGS.items() if a[1] == "x"}

    def agg(keys, x, valid):
        t = Table(columns={"k": keys, "x": x}, valid=valid)
        return ops.aggregate(t, "k", aggs, num_groups)

    args = (jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.float32),
            jnp.ones((n,), bool))
    for batch, f, a in ((1, agg, args),
                        (3, jax.vmap(agg), [jnp.stack([c] * 3) for c in args])):
        count, gathers = _hlo_ops(jax.jit(f).lower(*a).compile())
        assert count == {"sort": 1, "scatter": 0}, count
        # every gather reads num_groups elements a request, none the n rows
        assert gathers and max(gathers) <= batch * num_groups, gathers


def test_union_all():
    rng = np.random.default_rng(5)
    a, b = mk_table(rng, 5), mk_table(rng, 7)
    u = ops.union_all(a, b)
    npo = oracle.union_all(a.to_numpy(), b.to_numpy())
    assert_tables_equal(u, npo)


# -- property tests ----------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(4, 40), seed=st.integers(0, 1000),
       thresh=st.floats(0.0, 10.0))
def test_prop_filter_matches_oracle(n, seed, thresh):
    rng = np.random.default_rng(seed)
    t = mk_table(rng, n, with_vec=False)
    mask = t["x"] > thresh
    ft = ops.filter_(t, mask)
    npo = oracle.filter_(t.to_numpy(), np.asarray(mask))
    assert_tables_equal(ft, npo)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(4, 30), m=st.integers(2, 10), seed=st.integers(0, 1000))
def test_prop_join_matches_oracle(n, m, seed):
    rng = np.random.default_rng(seed)
    left = Table.from_columns({
        "fk": jnp.asarray(rng.integers(0, m + 3, n), jnp.int32),
        "a": jnp.asarray(rng.random(n), jnp.float32)})
    right = Table.from_columns({
        "rid": jnp.arange(m, dtype=jnp.int32),
        "b": jnp.asarray(rng.random(m), jnp.float32)})
    j = ops.fk_join(left, right, "fk", "rid")
    npo = oracle.fk_join(left.to_numpy(), right.to_numpy(), "fk", "rid")
    assert_tables_equal(j, npo)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(4, 50), seed=st.integers(0, 1000))
def test_prop_aggregate_matches_oracle(n, seed):
    rng = np.random.default_rng(seed)
    t = mk_table(rng, n, with_vec=False)
    g = ops.aggregate(t, "k", {"s": ("sum", "x"), "c": ("count", "x")},
                      num_groups=n + 2)
    npo = oracle.aggregate(t.to_numpy(), "k",
                           {"s": ("sum", "x"), "c": ("count", "x")})
    assert_tables_equal(g, npo, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 12), m=st.integers(2, 8), seed=st.integers(0, 100))
def test_prop_cross_join_cardinality(n, m, seed):
    rng = np.random.default_rng(seed)
    a = mk_table(rng, n, False)
    b = mk_table(rng, m, False).rename({"id": "i2", "k": "k2", "x": "x2"})
    x = ops.cross_join(a, b)
    assert x.capacity == n * m
    assert int(x.num_valid()) == n * m


def test_canonical_orders_by_exact_columns_first():
    # "a_score" sorts before "id" by name; values within tolerance that round
    # to different 4-decimal keys must not pair up the wrong rows
    a = Table.from_columns({"a_score": jnp.asarray([0.12345, 0.12344]),
                            "id": jnp.asarray([7, 3], jnp.int32)})
    b = Table.from_columns({"a_score": jnp.asarray([0.12344, 0.12346]),
                            "id": jnp.asarray([7, 3], jnp.int32)})
    ca, cb = a.canonical(), b.canonical()
    np.testing.assert_array_equal(ca["id"], [3, 7])
    np.testing.assert_array_equal(ca["id"], cb["id"])
    np.testing.assert_allclose(ca["a_score"], cb["a_score"], atol=5e-5)
