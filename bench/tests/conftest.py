"""CPU tests of the benchmark itself, at sizes a test run holds:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

They sit outside the repository's ``testpaths``. ``tiny`` loads a cell as
``bench/run.py`` does and shrinks its configuration's scale (never its
widths) so that a run takes seconds on the CPU.
"""
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

TINY = {
    "movielens1m_rec": dict(users=64, movies=72, ratings=3000,
                            min_ratings_per_user=5, trending_movies=36,
                            trending_genre_movies=6,
                            # the CPU's default precision is float32
                            matmul_inputs="float32"),
    "creditcard_fraud": dict(rows=2048, trees=10, depth=4, score_rows=512),
}
PEAKS = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


@pytest.fixture(scope="session")
def bench_run():
    import run
    return run


@pytest.fixture
def tiny(bench_run):
    """``tiny(cell, root=...)``: the cell's spec at a test's size."""
    def load(cell, root=ROOT):
        spec = bench_run.load_cell(cell, root)
        spec["cfg"].update(TINY.get(spec["cell"]["config"], {}))
        return spec
    return load


@pytest.fixture
def run_tiny(bench_run):
    """``run_tiny(spec, seed, **kw)``: one run on the CPU, skipping only
    the look for a chip."""
    def go(spec, seed=3_500_000_001, seconds=0.5, **kw):
        return bench_run.run(spec, seed, seconds, peaks=PEAKS,
                             t_start=time.monotonic(), **kw)
    return go
