"""Per-operator device time on a small recorded trace: ops of an executable
the program compiled and recorded, named in the trace by their HLO names
under ``vmap(...)``-wrapped operator scopes, are summed by innermost
operator; an op the record does not know counts as unscoped, and a fusion
whose fused ops belong to two operators is counted apart too. The
program's spans inside a harness ``step`` leave the harness's attribution
as it was, and every new reader reads nothing from a record without a
trace or from a program that predates the record."""
import sys

import pytest
from jax.profiler import ProfileData

import trace as bench_trace

def _scoped_executable():
    """A plan-like executable, compiled and recorded as the program's plan
    cache records one: its ops under ``vmap(...)``-wrapped operator scopes,
    and a fusion of an aggregate's multiply into a projection's tanh."""
    import jax
    import jax.numpy as jnp
    from repro import tracing

    def body(x):
        with jax.named_scope("aggregate"):
            y = jnp.sin(x) * 2
        with jax.named_scope("project"):
            return jnp.tanh(y), jnp.cumsum(x)

    x = jnp.ones((4, 64))
    compiled = jax.jit(jax.vmap(body)).lower(x).compile()
    tracing.record_executable("bench-test", 1, compiled)
    return tracing.executables()[-1]


def _xspace(op_names):
    """Four device ops one after another, 1,000 ns each, in a window of
    10,000 ns, then a host step holding the program's spans."""
    meta = "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(op_names))
    evs = "".join(f"    events {{ metadata_id: {i + 1} offset_ps: "
                  f"{(1 + 2 * i) * 1_000_000} duration_ps: 1000000 }}\n"
                  for i in range(len(op_names)))
    host = [("window", 0, 10_000), ("step", 0, 10_000),
            ("serving.dispatch", 500, 9_500), ("executor.launch", 600, 900),
            ("executor.wait", 900, 9_000)]
    hmeta = "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{n}" }} }}\n' for i, (n, _, _) in enumerate(host))
    hevs = "".join(f"    events {{ metadata_id: {i + 1} offset_ps: {a * 1000}"
                   f" duration_ps: {(b - a) * 1000} }}\n"
                   for i, (_, a, b) in enumerate(host))
    return (f'planes {{\n  id: 1 name: "/device:TPU:0"\n'
            f'  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{evs}  }}\n'
            f'{meta}}}\nplanes {{\n  id: 2 name: "/host:CPU"\n'
            f'  lines {{ id: 1 name: "python" timestamp_ns: 0\n{hevs}  }}\n'
            f'{hmeta}}}\n')


def _scoped_record():
    exe = _scoped_executable()
    fusion = next(n for n, s in exe.ops.items() if s.cross)
    other = next(n for n, s in exe.ops.items()
                 if s.opcode != "fusion" and s.operator == "project"
                 and s.opcode not in ("parameter", "tuple"))
    names = [fusion, other, "unknown.7", f"%{fusion} = f32[4,64] fusion()"]
    profile = ProfileData.from_text_proto(_xspace(names))
    ops, spans, rest = bench_trace.events(
        profile, {"window", "submit", "step", "collect", "offer"})
    out = bench_trace.reduce(ops, spans, n_devices=1, other=rest)
    return {"trace": out, "completed": 2, "batch_sizes": {1: 2}}, fusion


def test_device_time_is_summed_by_innermost_operator():
    import scopes
    rec, fusion = _scoped_record()
    got = scopes.by_operator(rec)
    # the cross-operator fusion runs twice (2,000 ns) and goes to its root,
    # the projection, as does the other projection op; one op is unknown
    assert got["operators"] == pytest.approx({"project": 3_000e-9})
    assert got["unscoped"] == pytest.approx(1_000e-9)
    assert got["cross"] == pytest.approx(2_000e-9)
    assert scopes.ms_per_query(rec, "project") == pytest.approx(1.5e-3)
    assert scopes.ms_per_query(rec, "aggregate") == 0.0


def test_program_spans_leave_the_harness_attribution_as_it_was():
    rec, _ = _scoped_record()
    # gaps 0..1,000 and 8,000..10,000 lie in the harness's step, whatever
    # program spans run inside it; none is a millisecond long
    assert rec["trace"]["idle_gaps"] == pytest.approx(
        {"step": 6_000e-9})
    assert rec["trace"]["busy_s"] == pytest.approx(4_000e-9)


def _without_tracing(monkeypatch):
    """The program as it was before it recorded spans and operators."""
    import repro
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)


@pytest.mark.parametrize("reader", ["aggregate_ms", "cross_join_ms",
                                    "compact_ms", "project_ms"])
def test_operator_readers_read_nothing_without_a_trace(reader, bench_run,
                                                        monkeypatch):
    from conftest import BENCH
    mod = bench_run.load_module(BENCH / "metrics" / f"{reader}.py")
    rec = {"trace": None, "completed": 3, "batch_sizes": {1: 3}}
    assert mod.read(rec) is None
    # a program without the operator record (one that predates it)
    traced, _ = _scoped_record()
    _without_tracing(monkeypatch)
    assert mod.read(traced) is None


def test_optimizer_cost_reads_the_program_total(bench_run, monkeypatch):
    from conftest import BENCH
    from repro import tracing
    mod = bench_run.load_module(BENCH / "metrics" / "optimizer_cost_s.py")
    with tracing.span("optimizer.cost"):
        pass
    assert mod.read({}) == tracing.published_s["optimizer.cost"] > 0
    _without_tracing(monkeypatch)
    assert mod.read({}) is None
