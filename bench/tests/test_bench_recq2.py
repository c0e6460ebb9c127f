"""The cell ``recq2.closed1`` at a tiny size: the program agrees with the
plain reference, the control (the reference in bfloat16, put in the
program's place) fails a limit, and two faults each come out as rows off:
a compaction bound one row short, and one pair beyond ``flip_logit``
forced to the other side of the interest filter."""
import math

import numpy as np
import pytest

from repro.core.rules import o1
from repro.serving import executor

CELL = "recq2.closed1"
# scale cut to a test's size; widths kept but the tag vector's, and the
# autoencoder's input with it; the CPU's default precision is float32
TINY = dict(users=40, movies=48, tag_dim=64, autoencoder=[64, 2048, 256],
            trending_movies=24, interest_pairs=576, matmul_inputs="float32")
SEED = 2**35 + 9


@pytest.fixture
def spec(tiny):
    s = tiny(CELL)
    s["cfg"].update(TINY)
    return s


def test_tiny_cell_is_correct(spec, run_tiny):
    rec = run_tiny(spec, seed=SEED)
    assert rec["correct"], rec["checks"]
    assert rec["completed"] > 0 and rec["failed"] == 0
    assert rec["compiles_in_window"] == 0


def test_control_in_bfloat16_fails(spec, run_tiny):
    rec = run_tiny(spec, seed=SEED + 1, control=True)
    limits = spec["cfg"]["limits"]
    failed = [k for k, v in rec["control"].items()
              if not v <= limits[k] or math.isnan(v)]
    assert failed, rec["control"]


def test_compaction_bound_one_row_short_is_rows_off(spec, run_tiny,
                                                    monkeypatch):
    """The trending filter's compaction sized from a count one short of
    its passing movies, at the count itself: one movie's pairs drop."""
    row_bound = o1.CompactAfterFilter._row_bound

    def short(self, f, plan, catalog):
        n = row_bound(self, f, plan, catalog)
        return None if n is None else n - 1

    monkeypatch.setattr(o1.CompactAfterFilter, "_row_bound", short)
    monkeypatch.setattr(o1.CompactAfterFilter, "_count_cache", {})
    monkeypatch.setattr(o1, "_round_up", lambda n: int(n))
    rec = run_tiny(spec, seed=SEED)
    assert rec["checks"]["rows_off"]["value"] > 0, rec["checks"]
    assert not rec["correct"]


def test_pair_beyond_flip_logit_on_the_other_side_is_rows_off(
        spec, run_tiny, monkeypatch):
    """Each answer loses the kept pair farthest above the interest
    threshold, as if the filter had put it on the other side."""
    cfg = spec["cfg"]
    built = spec["config"].build(cfg, SEED, 8)
    far = {}
    for p, raw in enumerate(built["raw"]):
        ref = spec["reference"].reference(cfg, raw, built["weights"], SEED)
        trend, interest = spec["reference"]._gaps(cfg, ref)
        gap = np.where((trend > 0)[None, :], interest, -np.inf)
        u, m = np.unravel_index(np.argmax(gap), gap.shape)
        assert gap[u, m] > cfg["limits"]["flip_logit"]
        first = np.asarray(raw["users"]["user_f"])[0]
        far[first.tobytes()] = (u, m)
    dispatch = executor.BatchedExecutor.dispatch

    def broken(self, batch):
        dt = dispatch(self, batch)
        for r in batch.requests:
            first = np.asarray(r.tables["users"].columns["user_f"])[0]
            u, m = far[first.tobytes()]
            t = r.result
            hit = (t.columns["user_id"] == u) & (t.columns["movie_id"] == m)
            t.valid = t.valid & ~hit
        return dt

    monkeypatch.setattr(executor.BatchedExecutor, "dispatch", broken)
    rec = run_tiny(spec, seed=SEED)
    assert rec["checks"]["rows_off"]["value"] > 0, rec["checks"]
    assert rec["checks"]["flip_logit"]["value"] > cfg["limits"]["flip_logit"]
    assert not rec["correct"]
