"""End-to-end recommendation workload (paper Sec. V-C1): the MovieLens
complex queries optimized by the *reusable* MCTS with trained Query2Vec
embeddings — including the state-collision speedup on repeated templates.

    PYTHONPATH=src python examples/recommendation_pipeline.py
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.compile_cache import enable_compile_cache
from repro.core import optimizer as om
from repro.core.executor import execute
from repro.core.mcts import ReusableMCTS
from repro.core.planner import analytic_cost_fn
from repro.data import templates
from repro.mlfuncs import builders
from benchmarks.common import time_plan


def main():
    print("training Model2Vec/Query2Vec (contrastive, WL-mined pairs) ...")
    emb = om.init_embedder(0)
    graphs = [g for g in (builders.sample_model(s).graph for s in range(24))
              if g is not None]
    om.train_model2vec(emb, graphs, steps=40, batch=8, lr=1e-4)
    plans, cats, costs = [], [], []
    for i in range(24):
        p, c = templates.sample_query(1 + (i % 3), seed=500 + i, scale=0.5)
        plans.append(p)
        cats.append(c)
        costs.append(analytic_cost_fn(c)(p))
    om.train_query2vec(emb, plans, cats, steps=40, batch=8)
    om.train_latency(emb, plans, cats, costs, steps=80, batch=8)

    opt = ReusableMCTS(catalog_fn=None, embed_fn=emb.embed,
                       cost_fn_factory=lambda c: analytic_cost_fn(c),
                       iterations=25, warm_iterations=8, seed=0)

    print("\nquery                 opt_s   collision  est_speedup  wall_speedup")
    for i in range(6):
        plan, cat = templates.sample_query(1 + (i % 3), seed=900 + i, scale=0.5)
        t0 = time.perf_counter()
        best, stats = opt.optimize(plan, cat)
        opt_s = time.perf_counter() - t0
        base_t, _ = time_plan(plan, cat, repeats=1)
        opt_t, _ = time_plan(best, cat, repeats=1)
        a = execute(plan, cat).canonical()
        b = execute(best, cat).canonical()
        for k in a:
            np.testing.assert_allclose(a[k], b[k], rtol=5e-4, atol=5e-4)
        print(f"rec_template_{1 + (i % 3)} run{i:02d}   {opt_s:6.2f}   "
              f"{str(stats['collision']):>5}     {stats['speedup']:6.2f}x"
              f"      {base_t / max(opt_t, 1e-9):6.2f}x")
    print(f"\ncollision rate: {opt.collision_rate:.2f}  "
          f"node store: {len(opt.nodes)} states, {opt.storage_bytes()}B")


if __name__ == "__main__":
    enable_compile_cache()
    main()
