"""Quickstart: build a catalog, register an ML model, write an inference
query in the three-level IR, optimize it with MCTS, execute, verify.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import ir
from repro.core.executor import execute
from repro.core.planner import analytic_cost_fn, optimize_vanilla_mcts, timed
from repro.mlfuncs import builders
from repro.mlfuncs.registry import Registry
from repro.relational.table import Table


def main():
    rng = np.random.default_rng(0)

    # 1. base tables (paper Fig. 3: preprocessed user/movie features)
    users = Table.from_columns({
        "user_id": jnp.arange(200, dtype=jnp.int32),
        "age": jnp.asarray(rng.integers(18, 80, 200), jnp.float32),
        "user_f": jnp.asarray(rng.standard_normal((200, 32)), jnp.float32)})
    movies = Table.from_columns({
        "movie_id": jnp.arange(80, dtype=jnp.int32),
        "genre": jnp.asarray(rng.integers(0, 18, 80), jnp.int32),
        "movie_f": jnp.asarray(rng.standard_normal((80, 16)), jnp.float32)})
    catalog = ir.Catalog()
    catalog.add("users", users)
    catalog.add("movies", movies)

    # 2. load + register the two-tower model (Fig. 3 steps 1-2)
    registry = Registry()
    registry.register(builders.two_tower("two_tower", [32, 64, 16],
                                         [16, 64, 16], seed=1))
    trending = builders.ffnn("trending", [16, 32, 1], seed=2)
    trending.selectivity_hint = 0.5
    registry.register(trending)

    # 3. the inference query (Fig. 3 step 3): filter movies, cross join
    #    users, score each pair with the two-tower model
    query = ir.Project(
        ir.Filter(
            ir.Filter(
                ir.CrossJoin(ir.Scan("users"), ir.Scan("movies")),
                pred=ir.IsIn(ir.Col("genre"), (1, 4, 7))),
            pred=ir.Cmp(">", ir.Call("trending", (ir.Col("movie_f"),)),
                        ir.Const(0.5))),
        outputs=(("score", ir.Call("two_tower",
                                   (ir.Col("user_f"), ir.Col("movie_f")))),),
        keep=("user_id", "movie_id"))
    plan = ir.Plan(query, registry)

    # 4. optimize (reusable-MCTS action space: R1/R2/R3/R4 rules)
    cost_fn = analytic_cost_fn(catalog)
    optimized, stats = timed(optimize_vanilla_mcts, plan, catalog,
                             cost_fn=cost_fn, iterations=40)
    print(f"estimated cost: {cost_fn(plan):.3e}s -> {cost_fn(optimized):.3e}s"
          f"  ({stats['speedup']:.1f}x, optimized in {stats['opt_seconds']:.2f}s)")

    # 5. execute both (execute lowers to the physical plan layer and runs
    #    the fused pipelines), verify equivalence
    a = execute(plan, catalog).canonical()
    b = execute(optimized, catalog).canonical()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=5e-4, atol=5e-4)
    print(f"results identical on {len(a['score'])} scored pairs — "
          "co-optimization is lossless.")

    # 6. serve repeated traffic through the compiled-plan cache: a second
    #    structurally identical query skips lowering AND jax tracing
    from repro.core.plan_cache import PlanCache
    cache = PlanCache()
    tables = dict(catalog.tables)
    cache.get_or_compile(optimized, catalog)(tables)   # miss: lower + trace
    cache.get_or_compile(optimized, catalog)(tables)   # hit: dispatch only
    s = cache.stats
    print(f"plan cache: hits={s.hits} misses={s.misses} "
          f"traces={cache.traces} (1 trace for 2 executions)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
