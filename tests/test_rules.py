"""Every co-optimization rule must preserve query results (O1-O4) —
per-config equivalence + chained-rewrite equivalence + hypothesis random
rule sequences."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")  # property tests degrade to skips
from hypothesis import given, settings, strategies as st

from repro.relational.table import Table
from repro.mlfuncs import builders
from repro.mlfuncs.registry import Registry
from repro.core import ir
from repro.core.executor import execute
from repro.core.rules import ALL_RULES


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(0)
    N, M = 40, 16
    users = Table.from_columns({
        "user_id": jnp.arange(N, dtype=jnp.int32),
        "age": jnp.asarray(rng.integers(18, 80, N), jnp.float32),
        "user_f": jnp.asarray(rng.standard_normal((N, 12)), jnp.float32)})
    movies = Table.from_columns({
        "movie_id": jnp.arange(M, dtype=jnp.int32),
        "genre": jnp.asarray(rng.integers(0, 5, M), jnp.int32),
        "movie_f": jnp.asarray(rng.standard_normal((M, 8)), jnp.float32)})
    cat = ir.Catalog()
    cat.add("users", users)
    cat.add("movies", movies)
    reg = Registry()
    reg.register(builders.two_tower("tt", [12, 16, 8], [8, 16, 8], seed=1))
    trend = builders.ffnn("trend", [8, 8, 1], seed=2)
    trend.selectivity_hint = 0.5
    reg.register(trend)
    reg.register(builders.concat_ffnn("cf", [12, 8], [16, 1], seed=3))
    reg.register(builders.decision_forest("forest", 6, 3, 12, seed=4))
    reg.register(builders.autoencoder_encoder("ae", 12, 4096, 4, seed=5))
    reg.register(builders.kmeans_assign("km", 4, 12, seed=6))
    root = ir.Project(
        child=ir.Filter(
            child=ir.Filter(
                child=ir.CrossJoin(ir.Scan("users"), ir.Scan("movies")),
                pred=ir.IsIn(ir.Col("genre"), (1, 2, 3))),
            pred=ir.Cmp(">", ir.Call("trend", (ir.Col("movie_f"),)),
                        ir.Const(0.4))),
        outputs=(("score", ir.Call("tt", (ir.Col("user_f"), ir.Col("movie_f")))),
                 ("cscore", ir.Call("cf", (ir.Col("user_f"), ir.Col("movie_f")))),
                 ("fpred", ir.Call("forest", (ir.Col("user_f"),))),
                 ("enc", ir.Call("ae", (ir.Col("user_f"),))),
                 ("cluster", ir.Call("km", (ir.Col("user_f"),)))),
        keep=("user_id", "movie_id"))
    plan = ir.Plan(root, reg)
    base = execute(plan, cat).canonical()
    return plan, cat, base


def check_equal(a, b, label=""):
    assert set(a) == set(b), f"{label}: schema {sorted(set(a) ^ set(b))}"
    for k in a:
        assert a[k].shape == b[k].shape, f"{label}:{k} shape"
        np.testing.assert_allclose(a[k], b[k], rtol=5e-4, atol=5e-4,
                                   err_msg=f"{label}:{k}")


@pytest.mark.parametrize("rule_name", sorted(ALL_RULES))
def test_rule_preserves_results(setup, rule_name):
    plan, cat, base = setup
    rule = ALL_RULES[rule_name]
    cfgs = rule.configs(plan, cat)
    for cfg in cfgs[:6]:
        p2 = rule.apply(plan, cat, cfg)
        out = execute(p2, cat).canonical()
        check_equal(base, out, f"{rule_name} {dict(cfg.params)}")


def test_rules_have_coverage(setup):
    """The representative query must exercise most of the action space."""
    plan, cat, _ = setup
    applicable = {n for n, r in ALL_RULES.items() if r.configs(plan, cat)}
    assert {"R1-1", "R1-2", "R1-4-merge", "R2-1", "R3-1", "R3-2", "R3-3",
            "R4-1-fuse", "R4-1-split", "R4-2"} <= applicable


def test_chained_split_pushdown(setup):
    """Paper Fig. 4: split two-tower, push towers below the cross join."""
    plan, cat, base = setup
    for _ in range(2):
        cfgs = ALL_RULES["R4-1-split"].configs(plan, cat)
        if not cfgs:
            break
        plan = ALL_RULES["R4-1-split"].apply(plan, cat, cfgs[0])
    for rn in ["R1-2", "R1-3"]:
        for _ in range(8):
            cfgs = ALL_RULES[rn].configs(plan, cat)
            if not cfgs:
                break
            plan = ALL_RULES[rn].apply(plan, cat, cfgs[0])
    out = execute(plan, cat).canonical()
    check_equal(base, out, "chained")


def test_unfuse_roundtrip(setup):
    plan, cat, base = setup
    cfgs = ALL_RULES["R4-1-fuse"].configs(plan, cat)
    plan2 = ALL_RULES["R4-1-fuse"].apply(plan, cat, cfgs[0])
    cfgs2 = ALL_RULES["R4-1-unfuse"].configs(plan2, cat)
    assert cfgs2
    plan3 = ALL_RULES["R4-1-unfuse"].apply(plan2, cat, cfgs2[0])
    check_equal(base, execute(plan3, cat).canonical(), "fuse/unfuse")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_prop_random_rule_sequences(setup, seed):
    """Random sequences of rule applications never change results."""
    plan, cat, base = setup
    rng = np.random.default_rng(seed)
    names = sorted(ALL_RULES)
    cur = plan
    for _ in range(4):
        name = names[int(rng.integers(0, len(names)))]
        cfgs = ALL_RULES[name].configs(cur, cat)
        if not cfgs:
            continue
        cfg = cfgs[int(rng.integers(0, len(cfgs)))]
        cur = ALL_RULES[name].apply(cur, cat, cfg)
    out = execute(cur, cat).canonical()
    check_equal(base, out, f"seq seed={seed}")


def test_r21_offers_its_cross_join_config_exactly_where_it_rewrites(setup):
    """R2-1's cheap check over a cross join (a matmul over a concat of both
    sides above it) agrees with the separation itself, on the query above,
    the workloads, and each plan one rule step from them."""
    from repro.core.rules import base, o2
    from repro.data import workloads
    roots = [setup[:2]] + [
        (w.plan, w.catalog) for w in (workloads.ALL_WORKLOADS[n](scale=0.3)
                                      for n in sorted(workloads.ALL_WORKLOADS))
        if any(isinstance(n, ir.CrossJoin) for n in ir.walk(w.plan.root))]
    cases = list(roots)
    for plan, cat in roots:
        for rule in ALL_RULES.values():
            cases += [(rule.apply(plan, cat, c), cat)
                      for c in rule.configs(plan, cat)[:1]]
    seen = set()
    for plan, cat in cases:
        for p in base.all_paths(plan.root):
            if isinstance(base.node_at(plan.root, p), ir.CrossJoin):
                offered = o2._factorizes_across(plan, cat, p)
                assert offered == (
                    o2._separate_cross_join(plan, cat, p) is not None), p
                seen.add(offered)
    assert seen == {True, False}
