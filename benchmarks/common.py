"""Benchmark helpers: wall-clock measurement of compiled plans."""
from __future__ import annotations

import time
from typing import Callable, Optional, Tuple

import jax

from repro.core import ir
from repro.core import physical as ph
from repro.core.lowering import lower
from repro.core.plan_cache import PlanCache


def time_plan(plan: ir.Plan, catalog: ir.Catalog, repeats: int = 3,
              cache: Optional[PlanCache] = None) -> Tuple[float, float]:
    """Returns (median wall seconds, compile seconds).

    Goes through the physical path (lower + jit). With ``cache`` given the
    compiled executable is shared/reused through the plan cache, so the
    compile-seconds of a repeated plan collapse to a cache lookup.
    """
    tables = dict(catalog.tables)
    if cache is not None:
        run_tables = cache.get_or_compile(plan, catalog)
    else:
        # tables are arguments, as in PlanCache: closed over, they would be
        # baked into the compiled program as constants
        pplan = lower(plan, catalog)
        run_tables = jax.jit(lambda t: ph.run(pplan, t))
    run = lambda: run_tables(tables)

    t0 = time.perf_counter()
    out = run()
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = run()
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], compile_s


def best_time(fn: Callable, repeats: int = 9) -> float:
    """Min over repeats: the standard noise-robust microbenchmark estimator
    (load spikes only ever add time). The first call runs outside the
    window, warming/compiling whatever the closure touches."""
    jax.block_until_ready(fn())
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def time_fn(fn: Callable, *args, repeats: int = 5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def csv_line(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.1f},{derived}"
