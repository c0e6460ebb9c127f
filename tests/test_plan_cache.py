"""Compiled-plan cache + LRU machinery + embedder cache bounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor, ir
from repro.core.plan_cache import LRUCache, PlanCache, schema_signature
from repro.mlfuncs import builders
from repro.mlfuncs.registry import Registry
from repro.relational.table import Table


def _mini_setup(seed=0, n=32):
    """Fresh data per seed; the registered model is the same (the cache's
    contract: a registered fn name is a stable identity, same name ⇒ same
    weights, as in a model registry)."""
    rng = np.random.default_rng(seed)
    t = Table.from_columns({
        "id": jnp.arange(n, dtype=jnp.int32),
        "x": jnp.asarray(rng.uniform(0, 10, n), jnp.float32),
        "f": jnp.asarray(rng.standard_normal((n, 8)), jnp.float32)})
    cat = ir.Catalog()
    cat.add("t", t)
    reg = Registry()
    reg.register(builders.ffnn("m", [8, 16, 1], seed=1))
    root = ir.Project(
        ir.Filter(ir.Scan("t"), pred=ir.Cmp(">", ir.Col("x"), ir.Const(3.0))),
        outputs=(("score", ir.Call("m", (ir.Col("f"),))),),
        keep=("id",))
    return ir.Plan(root, reg), cat


def test_repeated_identical_query_hits_without_retrace():
    cache = PlanCache()
    plan1, cat1 = _mini_setup(seed=0)
    fn1 = cache.get_or_compile(plan1, cat1)
    out1 = fn1(dict(cat1.tables))
    jax.block_until_ready(out1)
    assert cache.stats.misses == 1 and cache.traces == 1

    # a structurally identical query built from scratch (fresh tree, fresh
    # registry, fresh — but same-shaped — data): hit, zero re-traces
    plan2, cat2 = _mini_setup(seed=7)
    fn2 = cache.get_or_compile(plan2, cat2)
    out2 = fn2(dict(cat2.tables))
    jax.block_until_ready(out2)
    assert cache.stats.hits == 1
    assert cache.traces == 1, "second structurally identical query re-traced"
    assert fn2 is fn1

    # and it computed the *fresh* data, not the cached plan's data
    ref2 = executor.execute(plan2, cat2)
    np.testing.assert_allclose(out2.canonical()["score"],
                               ref2.canonical()["score"], rtol=1e-5, atol=1e-6)


def test_different_structure_or_schema_misses():
    cache = PlanCache()
    plan, cat = _mini_setup()
    cache.get_or_compile(plan, cat)
    # different predicate constant -> different signature
    other = ir.Plan(ir.Filter(ir.Scan("t"),
                              pred=ir.Cmp(">", ir.Col("x"), ir.Const(5.0))),
                    plan.registry)
    cache.get_or_compile(other, cat)
    assert cache.stats.misses == 2
    # different capacity -> different schema signature
    _, cat2 = _mini_setup(n=64)
    assert schema_signature(cat) != schema_signature(cat2)
    cache.get_or_compile(plan, cat2)
    assert cache.stats.misses == 3
    # same fn name, different architecture -> different registry signature
    reg2 = Registry()
    reg2.register(builders.ffnn("m", [8, 32, 1], seed=1))  # wider hidden
    plan_arch = ir.Plan(plan.root, reg2)
    cache.get_or_compile(plan_arch, cat)
    assert cache.stats.misses == 4


def test_unscanned_catalog_table_does_not_over_key():
    """Regression: ``PlanCache.key`` used to hash the schema of *every*
    catalog table, so adding an unrelated table false-missed the cache and
    retraced. The key is restricted to the plan's scanned tables: the same
    plan over catalog +- an unscanned table is one entry, one trace."""
    cache = PlanCache()
    plan, cat = _mini_setup(seed=0)
    fn1 = cache.get_or_compile(plan, cat)
    jax.block_until_ready(fn1(dict(cat.tables)))
    assert cache.stats.misses == 1 and cache.traces == 1

    # same plan, catalog with an extra table the plan never scans
    plan2, cat2 = _mini_setup(seed=3)
    cat2.add("unrelated", Table.from_columns(
        {"k": jnp.arange(5, dtype=jnp.int32)}))
    assert schema_signature(cat) != schema_signature(cat2)  # full-catalog view
    assert cache.key(plan, cat) == cache.key(plan2, cat2)   # restricted key
    fn2 = cache.get_or_compile(plan2, cat2)
    jax.block_until_ready(fn2(dict(cat2.tables)))
    assert fn2 is fn1, "unscanned table false-missed the cache"
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert cache.traces == 1, "unscanned table forced a retrace"
    assert len(cache._cache) == 1

    # removing the unrelated table again is still the same entry
    fn3 = cache.get_or_compile(plan2, cat)
    assert fn3 is fn1 and cache.stats.hits == 2

    # but a *scanned* table's shape still keys: capacity change must miss
    _, cat_big = _mini_setup(n=64)
    cache.get_or_compile(plan, cat_big)
    assert cache.stats.misses == 2


def test_compile_plan_goes_through_cache():
    plan, cat = _mini_setup()
    cache = PlanCache()
    run = executor.compile_plan(plan, cat, cache=cache)
    a = run().canonical()
    run2 = executor.compile_plan(plan, cat, cache=cache)
    b = run2().canonical()
    assert cache.stats.hits == 1 and cache.traces == 1
    np.testing.assert_allclose(a["score"], b["score"])


def test_fallbacks_are_counted_and_served_stats_report_them():
    """A request served by something other than what it asked for counts in
    ``PlanCache.fallbacks``: on a one-device mesh the sharded and the
    partitioned entry points serve the plain executables, and a memory
    budget nothing fits prunes every lowering candidate."""
    from repro.core.mesh import data_mesh
    from repro.serving import QueryServer

    plan, cat = _mini_setup()
    one = data_mesh(1)
    cache = PlanCache()
    cache.get_or_compile_sharded(plan, cat, 2, one)
    cache.get_or_compile_partitioned(plan, cat, one)
    assert cache.fallbacks == {"sharded": 1, "partitioned": 1}

    server = QueryServer(max_batch_size=1, memory_budget=64.0)
    req = server.submit(plan, cat, dict(cat.tables))
    server.drain()
    assert req.error is None
    assert server.stats()["fallbacks"] == {"budget_pruned_all": 1}
    assert QueryServer().stats()["fallbacks"] == {}


def test_lru_cache_bounds_and_stats():
    c = LRUCache(maxsize=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1          # refresh a
    c.put("c", 3)                   # evicts b (LRU)
    assert c.stats.evictions == 1
    assert "b" not in c and "a" in c and "c" in c
    assert c.get("b") is None
    assert c.stats.hits == 1 and c.stats.misses == 1
    assert len(c) == 2


def test_lru_cache_eviction_order_under_interleaved_get_put():
    c = LRUCache(maxsize=3)
    c.put("a", 1)
    c.put("b", 2)
    c.put("c", 3)
    assert c.get("a") == 1          # order now b, c, a
    c.put("b", 20)                  # refresh by put: order c, a, b
    c.put("d", 4)                   # evicts c (true LRU, not insert order)
    assert "c" not in c and "a" in c and "b" in c and "d" in c
    assert c.get("c") is None
    c.put("e", 5)                   # evicts a (oldest touch)
    assert "a" not in c and "b" in c and "d" in c and "e" in c
    assert c.get("b") == 20         # refreshed value survived
    assert c.stats.evictions == 2


def test_lru_cache_clear_resets_contents_but_preserves_stats():
    c = LRUCache(maxsize=4)
    c.put("a", 1)
    assert c.get("a") == 1 and c.get("zz") is None
    hits, misses = c.stats.hits, c.stats.misses
    c.clear()
    assert len(c) == 0 and "a" not in c
    # stats survive a clear: the counters describe lifetime traffic
    assert c.stats.hits == hits and c.stats.misses == misses
    assert c.get("a") is None       # post-clear lookup is a miss
    assert c.stats.misses == misses + 1
    c.put("b", 2)                   # cache is usable again
    assert c.get("b") == 2


def test_lru_cache_maxsize_one_edge_case():
    c = LRUCache(maxsize=1)
    c.put("a", 1)
    c.put("b", 2)                   # immediately evicts a
    assert len(c) == 1 and "a" not in c and c.get("b") == 2
    assert c.stats.evictions == 1
    c.put("b", 3)                   # overwrite in place: no eviction
    assert c.get("b") == 3 and c.stats.evictions == 1
    # maxsize is clamped to >= 1 so the cache can always hold one entry
    assert LRUCache(maxsize=0).maxsize == 1
    assert LRUCache(maxsize=-5).maxsize == 1


def test_query_embedder_cache_is_bounded_with_stats():
    om = pytest.importorskip("repro.core.optimizer")
    emb = om.init_embedder(0)
    plan, cat = _mini_setup()
    e1 = emb.embed(plan, cat)
    e2 = emb.embed(plan, cat)
    np.testing.assert_allclose(e1, e2)
    assert emb.cache_stats.hits == 1 and emb.cache_stats.misses == 1
    assert emb._cache.maxsize == om.EMBED_CACHE_SIZE
