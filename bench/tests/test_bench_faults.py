"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the serving tier's executor, where the answers
are produced: an answer altered, half of a batch answered with the other
half's results, and a dispatch that hands back the previous answers
unchanged."""
import jax.numpy as jnp
import pytest

from repro.serving import executor


def _alter_one_answer(results):
    t = results[0]
    col = next(k for k in sorted(t.columns)
               if jnp.issubdtype(t.columns[k].dtype, jnp.floating))
    t.columns[col] = t.columns[col] + 0.25
    return results


def _half_batch(results):
    half = len(results) // 2
    return results[:half] + results[:len(results) - half]


class _Stale:
    def __init__(self):
        self.last = None

    def __call__(self, results):
        out = self.last if self.last is not None else results
        self.last = list(results)
        return out


FAULTS = {"answer_altered": lambda: _alter_one_answer,
          "half_batch": lambda: _half_batch,
          "stale_answers": _Stale}


@pytest.mark.parametrize("cell,fault", [
    ("rec.closed1", "answer_altered"), ("fraud.closed1", "answer_altered"),
    ("rec.closed1", "stale_answers"), ("fraud.closed1", "stale_answers"),
    ("rec.closed8", "half_batch"), ("rec.closed8", "answer_altered"),
])
def test_fault_is_not_correct(cell, fault, tiny, run_tiny, monkeypatch):
    plant = FAULTS[fault]()
    dispatch = executor.BatchedExecutor.dispatch

    def broken(self, batch):
        dt = dispatch(self, batch)
        results = plant([r.result for r in batch.requests])
        for r, res in zip(batch.requests, results):
            r.result = res
        return dt

    monkeypatch.setattr(executor.BatchedExecutor, "dispatch", broken)
    rec = run_tiny(tiny(cell), seed=5)
    assert not rec["correct"], rec["checks"]
