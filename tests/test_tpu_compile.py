"""Compile-only checks for a described TPU v5e chip (no chip needed).

The query path's Pallas kernels and the analytics_q1 plan with its forest
realized as the Pallas kernel are compiled at published widths by the TPU
compiler for a ``v5e:2x2`` topology described, not attached. Each compiled
program must hold the kernel (``tpu_custom_call``) and fit one chip's 16 GB.
This catches what interpret mode cannot: unaligned tiles, VMEM overuse,
programs that do not fit the device.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import dataclasses
import importlib.util
import json
import os
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import cost, ir, physical as ph
from repro.core.costed_lowering import lower_costed
from repro.core.lowering import lower
from repro.core.planner import analytic_cost_fn, optimize_vanilla_mcts
from repro.core.plan_cache import scan_table_names, stack_tables
from repro.core.rules import ALL_RULES
from repro.data import workloads
from repro.kernels import common
from repro.kernels.block_matmul import ops as bm_ops
from repro.kernels.decision_forest import ops as df_ops
from repro.kernels.fused_dense import ops as fd_ops

HBM_BYTES = 16e9  # one v5e chip
BUDGET = 15.75e9  # what one chip's allocator hands out, about
# the planner's analytic peak lies within this factor of the compiled
# program's device bytes (temporaries, arguments and outputs)
PEAK_FACTOR = 4.0
# rec_q1 at scale 60: 6,000 users x 512 compacted movies, scored per pair by
# the user tower's first layer (64 -> 300)
PAIRS, TOWER_IN, TOWER_HIDDEN = 6000 * 512, 64, 300


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_tpu(monkeypatch):
    """Kernels compiled, not interpreted, and no persistent compilation
    cache: an entry compiled for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(common, "use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _assert_compiled_kernel(jitted, *args):
    compiled = jitted.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"


def _assert_forest_kernel_compiles(one_chip, rows, d, n_trees, depth):
    nodes = 2 ** depth - 1
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _assert_compiled_kernel(
        jax.jit(df_ops.forest_predict),
        sds((rows, d), jnp.float32), sds((n_trees, nodes), jnp.int32),
        sds((n_trees, nodes), jnp.float32),
        sds((n_trees, nodes + 1), jnp.float32))


def test_decision_forest_kernel_at_creditcard_width(one_chip,
                                                    compiled_for_tpu):
    _assert_forest_kernel_compiles(one_chip, 289_000, 29, 100, 9)


def test_decision_forest_kernel_at_xgboost_fraud_width(one_chip,
                                                       compiled_for_tpu):
    _assert_forest_kernel_compiles(one_chip, 289_000, 32, 160, 6)


@pytest.mark.parametrize("kernel", ["block_matmul", "fused_dense"])
def test_matmul_kernels_at_two_tower_width(kernel, one_chip,
                                           compiled_for_tpu):
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=one_chip)
    x, w = sds((PAIRS, TOWER_IN)), sds((TOWER_IN, TOWER_HIDDEN))
    if kernel == "block_matmul":
        fn = jax.jit(lambda x, w: bm_ops.block_matmul(x, w, n_tiles=4))
        _assert_compiled_kernel(fn, x, w)
    else:
        fn = jax.jit(lambda x, w, b: fd_ops.fused_dense(x, w, b, "relu"))
        _assert_compiled_kernel(fn, x, w, sds((TOWER_HIDDEN,)))


def _forest_as_kernel(wl):
    """analytics_q1 with its forest realized as the Pallas kernel: R3-2,
    then R4-2 to 'fused', then R4-2 to 'pallas'."""
    plan = wl.plan
    for rule, want in (("R3-2", {}), ("R4-2", {"kind": "mode"}),
                       ("R4-2", {"kind": "node", "backend": "pallas"})):
        cfgs = [c for c in ALL_RULES[rule].configs(plan, wl.catalog)
                if all(c.get(k) == v for k, v in want.items())]
        plan = ALL_RULES[rule].apply(plan, wl.catalog, cfgs[0])
    return plan


@pytest.mark.parametrize("batch", [1, 4])
def test_analytics_q1_forest_kernel_plan_at_published_size(batch, one_chip,
                                                           compiled_for_tpu):
    wl = workloads.analytics_q1(scale=100)
    assert wl.catalog.stats["creditcard"].rows == 289_000
    plan = _forest_as_kernel(wl)
    pplan = lower(plan, wl.catalog)
    tables = {k: wl.catalog.tables[k] for k in scan_table_names(plan)}
    if batch == 1:
        fn = jax.jit(lambda t: ph.run(pplan, t))
        _assert_compiled_kernel(fn, _shapes(tables, one_chip))
    else:
        fn = jax.jit(lambda ts: jax.vmap(lambda t: ph.run(pplan, t))(
            stack_tables(list(ts))))
        _assert_compiled_kernel(
            fn, tuple(_shapes(tables, one_chip) for _ in range(batch)))


def test_rec_q2_plan_at_published_size(one_chip, compiled_for_tpu):
    """The plan the optimizer chooses for recommendation Q2 at MovieLens-1M
    size (``bench/configs/movielens1m_q2.py``), costed as on a v5e against
    its budget, compiles; the compiler's temporaries fit; the analytic peak
    is within ``PEAK_FACTOR`` of the compiled bytes; no tag column rides
    the 6,040 x 2,048 pairs."""
    configs = Path(__file__).resolve().parents[1] / "bench" / "configs"
    spec = importlib.util.spec_from_file_location(
        "q2_compile", configs / "movielens1m_q2.py")
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    cfg = json.loads((configs / "movielens1m_q2.json").read_text())
    built = gen.build(cfg, 11, 1)
    catalog = built["catalog"]
    profile = dataclasses.replace(cost.TPU_PROFILE, memory_budget=BUDGET)
    plan, _ = optimize_vanilla_mcts(
        built["plan"], catalog,
        cost_fn=analytic_cost_fn(catalog, profile),
        iterations=cfg["mcts_iterations"], seed=cfg["mcts_seed"])
    low = lower_costed(plan, catalog, profile=profile)
    assert not low.budget_pruned_all and low.peak_memory <= BUDGET
    join = next(n for n in ir.walk(plan.root) if isinstance(n, ir.CrossJoin))
    assert "mt_relevance" not in ir.infer(join, plan.registry, catalog).schema
    tables = {k: catalog.tables[k] for k in scan_table_names(plan)}
    compiled = jax.jit(lambda t: ph.run(low.plan, t)).lower(
        _shapes(tables, one_chip)).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < BUDGET
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert used / PEAK_FACTOR <= low.peak_memory <= used * PEAK_FACTOR, (
        low.peak_memory, used)
