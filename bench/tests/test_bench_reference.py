"""The program and the plain reference agree at a tiny size, and the
control (the reference in bfloat16, put in the program's place) fails."""
import math

import pytest

CELLS = ["rec.closed1", "fraud.closed1", "rec.closed8"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_matches_reference(cell, tiny, run_tiny):
    rec = run_tiny(tiny(cell), seed=2**33 + 5)
    assert rec["correct"], rec["checks"]
    assert rec["completed"] > 0 and rec["failed"] == 0
    assert rec["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", ["rec.closed1", "fraud.closed1"])
def test_control_in_bfloat16_fails(cell, tiny, run_tiny):
    spec = tiny(cell)
    rec = run_tiny(spec, seed=11, control=True)
    limits = spec["cfg"]["limits"]
    failed = [k for k, v in rec["control"].items()
              if not v <= limits[k] or math.isnan(v)]
    assert failed, rec["control"]


def test_reference_imports_nothing_of_the_program():
    import ast
    from conftest import BENCH
    for path in (BENCH / "configs").glob("*_reference.py"):
        tree = ast.parse(path.read_text())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom)]
        assert not [m for m in names if m.split(".")[0] == "repro"], path


def test_same_seed_same_data(tiny):
    import numpy as np
    spec = tiny("fraud.closed1")
    a = spec["config"].generate(spec["cfg"], 2**40 + 3, 2)
    b = spec["config"].generate(spec["cfg"], 2**40 + 3, 2)
    c = spec["config"].generate(spec["cfg"], 3, 2)
    # payload 0's tables
    fa, fb, fc = (np.asarray(x[0][0][0]["creditcard"]["cc_f"])
                  for x in (a, b, c))
    assert np.array_equal(fa, fb) and not np.array_equal(fa, fc)


def _rec_answer(spec, seed):
    import jax
    cfg = spec["cfg"]
    built = spec["config"].build(cfg, seed, 1)
    ref = spec["reference"].reference(cfg, built["raw"][0], built["weights"],
                                      seed)
    return jax.tree_util.tree_map(lambda x: x, ref)


@pytest.mark.parametrize("times_limit,forgiven", [(0.5, True), (4.0, False)])
def test_movie_on_the_other_side_of_the_threshold(tiny, times_limit,
                                                  forgiven):
    """A kept-genre movie that the program drops although the reference
    keeps it: within the flip_logit limit of the threshold it may go
    either way; beyond it, every user's row for it is off and flip_logit
    reads its distance."""
    import numpy as np
    spec = tiny("rec.closed1")
    cfg, ref_mod = spec["cfg"], spec["reference"]
    ref = _rec_answer(spec, 7)
    assert ref_mod.compare(cfg, ref_mod.as_rows(cfg, ref), ref)["rows_off"] == 0
    t, limit = cfg["trending_threshold"], cfg["limits"]["flip_logit"]
    m = int(np.flatnonzero(ref["genre_ok"] & (ref["p"] > t))[0])
    d = times_limit * limit
    ref = dict(ref, p=ref["p"].copy())
    ref["p"][m] = 1 / (1 + np.exp(-(np.log(t / (1 - t)) + d)))
    dropped = dict(ref, p=ref["p"].copy())
    dropped["p"][m] = 0.0
    out = ref_mod.compare(cfg, ref_mod.as_rows(cfg, dropped), ref)
    assert out["flip_logit"] == pytest.approx(d, rel=1e-3)
    assert out["rows_off"] == (0 if forgiven else int(ref["user_ok"].sum()))
    assert ref_mod.describe(cfg, ref)["within_limit"] >= (1 if forgiven else 0)


def test_trending_threshold_midway_at_stated_precision(tiny):
    """The generator sets the trending DNN's bias so that exactly
    trending_movies movies, trending_genre_movies of them in a kept genre,
    score above the threshold in the reference."""
    spec = tiny("rec.closed1")
    cfg = spec["cfg"]
    ref = _rec_answer(spec, 2**35 + 9)
    above = ref["p"] > cfg["trending_threshold"]
    assert int(above.sum()) == cfg["trending_movies"]
    assert int((above & ref["genre_ok"]).sum()) == cfg["trending_genre_movies"]
