"""Credit-card fraud scoring, the CactusDB paper's analytics Q1.

One scan of the transactions table, the filters ``amount < 800`` and
``time > 2``, and a 100-tree depth-9 forest over the 29 features of each
row that passes. Sizes are in ``creditcard_fraud.json``; the plain
reference is in ``creditcard_fraud_reference.py``.

Every seed does the same work, on the same compiled programs. The forest
is fixed, as a deployment's model is: it is drawn from a key that does not
depend on the seed, and the program compiles it into its executables, so
a forest that changed with the seed would recompile every run. The
(amount, time) pairs are one fixed multiset which the seed only deals to
rows, so the same number of rows passes the filters: amounts and times
are the quantiles of the public table's marginals (``amount_quantiles``,
``time_quantiles``), taken at evenly spaced shares of the rows. The seed
draws the features. Payload ``i`` of a run deals the pairs anew and draws
new features.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ir
from repro.mlfuncs.functions import Atom, MLFunction, MLGraph, MLNode
from repro.mlfuncs.registry import Registry
from repro.relational.table import Table

FIXED_KEY = 20260219  # the forest and the fixed multiset, never the seed


def prng(seed: int):
    """A key for any non-negative seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _quantiles(q, knots, log_above_first):
    """The value at share ``q`` of the rows, from ``(share, value)`` knots:
    linear between knots, or linear in the logarithm from the second knot
    on where ``log_above_first`` (a heavy tail whose first knot is 0)."""
    kq = jnp.asarray([k[0] for k in knots], jnp.float32)
    kv = jnp.asarray([k[1] for k in knots], jnp.float32)
    if not log_above_first:
        return jnp.interp(q, kq, kv)
    head = kv[0] + (kv[1] - kv[0]) * (q - kq[0]) / (kq[1] - kq[0])
    tail = jnp.exp(jnp.interp(q, kq[1:], jnp.log(kv[1:])))
    return jnp.where(q < kq[1], head, tail)


@functools.partial(jax.jit, static_argnums=tuple(range(2, 9)))
def _generate(key, fixed_key, n, d, trees, depth, amount_knots, time_knots,
              n_payloads):
    # the fixed key is an argument, not a constant, so that XLA does not
    # fold the forest into the program while compiling it
    q = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
    amount = _quantiles(q, amount_knots, True)
    time = _quantiles(q, time_knots, False)
    kf, kth, kl = jax.random.split(jax.random.fold_in(fixed_key, 1), 3)
    nodes = 2 ** depth - 1
    forest = {"feat": jax.random.randint(kf, (trees, nodes), 0, d),
              "thresh": jax.random.normal(kth, (trees, nodes)),
              "leaf": jax.random.normal(kl, (trees, nodes + 1))}

    def payload(i):
        kx, kp = jax.random.split(jax.random.fold_in(key, i))
        deal = jax.random.permutation(kp, n)
        return {"creditcard": {
            "cc_id": jnp.arange(n, dtype=jnp.int32),
            "amount": amount[deal],
            "time": time[deal],
            "cc_f": jax.random.normal(kx, (n, d)),
        }}, {"creditcard": jnp.ones((n,), bool)}

    stacked = jax.vmap(payload)(jnp.arange(n_payloads))
    # one output per payload: slicing them apart outside this program
    # would compile a small program per column and payload in every run
    return [jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
            for i in range(n_payloads)], forest


def generate(cfg: dict, seed: int, n_payloads: int):
    """``n_payloads`` payloads, each ``(tables, valid masks)``, and the
    forest, made on the device in one call from ``seed``."""
    def knots(name):
        return tuple((float(a), float(b)) for a, b in cfg[name])
    return _generate(prng(seed), jax.random.key(FIXED_KEY), cfg["rows"],
                     cfg["features"], cfg["trees"], cfg["depth"],
                     knots("amount_quantiles"), knots("time_quantiles"),
                     n_payloads)


def query(cfg: dict, forest) -> ir.Plan:
    """analytics_q1 as the program's IR (a copy, so that later edits to
    repro.data.workloads do not move this benchmark)."""
    reg = Registry()
    atom = Atom("forest", {"feat": np.asarray(forest["feat"], np.int32),
                           "thresh": np.asarray(forest["thresh"], np.float32),
                           "leaf": np.asarray(forest["leaf"], np.float32),
                           "depth": cfg["depth"]})
    reg.register(MLFunction(
        name="cc_forest", n_inputs=1,
        graph=MLGraph(nodes=[MLNode(id=0, atom=atom, args=(("in", 0),))],
                      out=0, n_inputs=1)))
    q = ir.Project(
        ir.Filter(
            ir.Filter(ir.Scan("creditcard"),
                      pred=ir.Cmp("<", ir.Col("amount"),
                                  ir.Const(float(cfg["amount_below"])))),
            pred=ir.Cmp(">", ir.Col("time"),
                        ir.Const(float(cfg["time_above"])))),
        outputs=(("fraud", ir.Call("cc_forest", (ir.Col("cc_f"),))),),
        keep=("cc_id",))
    return ir.Plan(q, reg)


def build(cfg: dict, seed: int, n_payloads: int) -> dict:
    """Everything a run needs: the query, its catalog (payload 0), every
    payload as the program's tables and as plain arrays for the reference,
    and the forest."""
    generated, forest = generate(cfg, seed, n_payloads)
    raw = [tables for tables, _ in generated]
    payloads = [{t: Table.from_columns(cols, valid=valid[t])
                 for t, cols in tables.items()}
                for tables, valid in generated]
    catalog = ir.Catalog()
    catalog.add("creditcard", payloads[0]["creditcard"])
    return {"plan": query(cfg, forest), "catalog": catalog,
            "payloads": payloads, "raw": raw, "weights": forest}


def work(cfg: dict, live: dict) -> dict:
    """The forest's work for one query, from the logical query and its live
    rows: one node test per live row, tree and level; bytes are the live
    feature rows, the tree tables (feature index, threshold, leaf) and the
    output."""
    nodes = 2 ** cfg["depth"] - 1
    rows = live["rows"]
    return {
        "model_flops": float(rows * cfg["trees"] * cfg["depth"]),
        "forest_ops": float(rows * cfg["trees"] * cfg["depth"]),
        "forest_bytes": float(rows * cfg["features"] * 4
                              + cfg["trees"] * (nodes * 8 + (nodes + 1) * 4)
                              + rows * 4),
    }
