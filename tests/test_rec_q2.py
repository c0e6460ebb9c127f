"""The paper's recommendation Q2 (``bench/configs/movielens1m_q2.py``) at a
tiny size: served through ``QueryServer`` after ``optimize_vanilla_mcts``,
every answer equals the plain reference under the configuration's limits,
and under a memory budget below the per-pair plan's analytic peak the
chosen plan runs the autoencoder below the cross join."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import cost, ir
from repro.core.planner import analytic_cost_fn, optimize_vanilla_mcts
from repro.serving import QueryServer

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
# scale cut to a test's size; widths kept but the tag vector's, and the
# autoencoder's input with it; the CPU's default precision is float32
TINY = dict(users=40, movies=48, tag_dim=64, autoencoder=[64, 2048, 256],
            trending_movies=24, interest_pairs=576, matmul_inputs="float32")


def _load(name):
    spec = importlib.util.spec_from_file_location(f"q2_{name}",
                                                  CONFIGS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def q2():
    cfg = json.loads((CONFIGS / "movielens1m_q2.json").read_text())
    cfg.update(TINY)
    built = _load("movielens1m_q2").build(cfg, 2**33 + 7, 3)
    return cfg, _load("movielens1m_q2_reference"), built


def _optimize(cfg, built, **kw):
    plan, _ = optimize_vanilla_mcts(
        built["plan"], built["catalog"],
        cost_fn=analytic_cost_fn(built["catalog"], **kw),
        iterations=cfg["mcts_iterations"], seed=cfg["mcts_seed"])
    return plan


def _reads_tags(fn, tag_dim) -> bool:
    """Whether ``fn`` holds a dense layer over the tag vector."""
    return fn.graph is not None and any(
        n.atom.kind in ("matmul", "fused_dense")
        and n.atom.params["w"].shape[0] == tag_dim for n in fn.graph.nodes)


def test_served_answers_equal_the_reference(q2):
    cfg, ref, built = q2
    plan = _optimize(cfg, built)
    server = QueryServer(max_batch_size=1, max_wait_s=3600.0)
    reqs = [server.submit(plan, built["catalog"], p)
            for p in built["payloads"]]
    server.drain()
    assert not any(server.stats()["fallbacks"].values())
    for req, raw in zip(reqs, built["raw"]):
        assert req.error is None, req.error
        valid = np.asarray(req.result.valid)
        rows = {k: np.asarray(v)[valid]
                for k, v in req.result.columns.items()}
        numbers = ref.compare(cfg, rows, ref.reference(cfg, raw,
                                                       built["weights"], 0))
        for k, v in numbers.items():
            assert v <= cfg["limits"][k], (k, v)


def test_autoencoder_runs_below_the_cross_join_under_a_budget(q2):
    cfg, _, built = q2
    catalog = built["catalog"]
    per_pair = cost.plan_peak_memory(built["plan"], catalog)
    budget = per_pair / 2
    plan = _optimize(cfg, built, memory_budget=budget)
    assert cost.plan_peak_memory(plan, catalog) <= budget
    assert (cost.plan_cost(plan, catalog, memory_budget=budget)
            < cost.plan_cost(built["plan"], catalog, memory_budget=budget))
    path = []
    node = plan.root
    while not isinstance(node, ir.CrossJoin):
        path.append(node)
        node = node.children()[0]
    assert "mt_relevance" not in ir.infer(node, plan.registry, catalog).schema
    for above in path:
        fns = [above.fn] if isinstance(above, ir.BlockedMatmul) else []
        exprs = ([above.pred] if isinstance(above, ir.Filter) else
                 [e for _, e in getattr(above, "outputs", ())])
        for e in exprs:
            stack = [e]
            while stack:
                x = stack.pop()
                if isinstance(x, ir.Call):
                    fns.append(x.fn)
                stack.extend(x.children())
        assert not any(_reads_tags(plan.registry.get(f), cfg["tag_dim"])
                       for f in fns), above
