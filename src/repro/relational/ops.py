"""Relational operators over static-shape columnar Tables.

Semantics (mask-aware):
  - ``filter``     : valid &= predicate(valid rows); never changes capacity.
  - ``compact``    : physically gathers valid rows to the front of a (usually
                     smaller) static capacity. This is how filter/project
                     pushdown pays off on TPU: downstream per-row ML compute
                     is proportional to *capacity*, not to live rows.
  - ``project``    : adds/overwrites columns (row-aligned compute).
  - ``fk_join``    : inner equi-join where the right side's key is unique
                     (dimension table). Output capacity == left capacity.
  - ``cross_join`` : cartesian product, capacity Na*Nb.
  - ``aggregate``  : group-by over one key column with sum/mean/count/min/max,
                     output capacity = static group bound.
  - ``union_all``  : concatenation.

All functions are jit-compatible and differentiable where meaningful.
"""
from __future__ import annotations

from typing import Callable, Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.relational.table import Table

_INT_SENTINEL = jnp.iinfo(jnp.int32).max


# ---------------------------------------------------------------------------
# filter / compact / project
# ---------------------------------------------------------------------------

def filter_(t: Table, mask: jax.Array) -> Table:
    """Keep rows where ``mask`` holds. ``mask`` is bool[capacity]."""
    return Table(columns=t.columns, valid=t.valid & mask)


def compact(t: Table, capacity: int) -> Table:
    """Gather valid rows to the front of a new static ``capacity``.

    If there are more valid rows than ``capacity`` the extra rows are dropped
    (the optimizer only compacts when its selectivity bound says this cannot
    happen; tests exercise the bound).
    """
    n = t.capacity
    # stable order: valid rows first, preserving relative order.
    order = jnp.argsort(jnp.where(t.valid, 0, 1), stable=True)
    take = order[:capacity] if capacity <= n else jnp.pad(order, (0, capacity - n))
    cols = {k: v[take] for k, v in t.columns.items()}
    rank = jnp.arange(capacity)
    nvalid = t.num_valid()
    valid = rank < jnp.minimum(nvalid, capacity)
    if capacity > n:
        valid = valid & (rank < n)
    return Table(columns=cols, valid=valid)


def project(t: Table, new_columns: Mapping[str, jax.Array], keep: Sequence[str] | None = None) -> Table:
    """Add/overwrite columns; optionally restrict the kept input columns."""
    base = t if keep is None else t.select(keep)
    return base.with_columns(dict(new_columns))


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def fk_join(left: Table, right: Table, left_key: str, right_key: str,
            rprefix: str = "") -> Table:
    """Inner FK equi-join: every left row matches <=1 valid right row.

    Right keys are assumed unique among valid rows (dimension table). Output
    rows align with left rows; unmatched left rows become invalid.
    """
    lk = jnp.asarray(left[left_key], jnp.int32)
    rk = jnp.asarray(right[right_key], jnp.int32)
    rk_m = jnp.where(right.valid, rk, _INT_SENTINEL)
    order = jnp.argsort(rk_m)
    sorted_keys = rk_m[order]
    pos = jnp.searchsorted(sorted_keys, lk)
    pos_c = jnp.clip(pos, 0, rk.shape[0] - 1)
    matched = (sorted_keys[pos_c] == lk) & (lk != _INT_SENTINEL)
    src = order[pos_c]
    cols = dict(left.columns)
    for name, col in right.columns.items():
        out_name = rprefix + name
        if out_name == left_key and name == right_key:
            continue  # join key identical; keep left copy
        cols[out_name] = col[src]
    valid = left.valid & matched & right.valid[src]
    return Table(columns=cols, valid=valid)


def cross_join(a: Table, b: Table, aprefix: str = "", bprefix: str = "") -> Table:
    """Cartesian product. Row (ia, ib) lands at index ia * Nb + ib."""
    na, nb = a.capacity, b.capacity

    # broadcast + reshape, not jnp.repeat/jnp.tile: repeat with a
    # total_repeat_length builds its gather indices from constants, which
    # XLA then folds at compile time (~50 s for a 6000 x 512 product on TPU)
    def repeat(x):
        return jnp.broadcast_to(x[:, None], (na, nb) + x.shape[1:]).reshape(
            (na * nb,) + x.shape[1:])

    def tile(x):
        return jnp.broadcast_to(x[None], (na,) + x.shape).reshape(
            (na * nb,) + x.shape[1:])

    cols: Dict[str, jax.Array] = {}
    for name, col in a.columns.items():
        cols[aprefix + name] = repeat(col)
    for name, col in b.columns.items():
        cols[bprefix + name] = tile(col)
    return Table(columns=cols, valid=repeat(a.valid) & tile(b.valid))


# ---------------------------------------------------------------------------
# aggregate
# ---------------------------------------------------------------------------

_AGG_KINDS = ("sum", "mean", "count", "min", "max")

# per kind: the scan's combining op and the value of a group slot left empty
_AGG_SCAN = {"sum": (jnp.add, 0.0), "mean": (jnp.add, 0.0),
             "min": (jnp.minimum, jnp.inf), "max": (jnp.maximum, -jnp.inf)}


def _segmented_scan(x: jax.Array, start: jax.Array, op, empty) -> jax.Array:
    """Inclusive scan of ``op`` down axis 0 of ``x``, restarting where ``start``.

    Log-step (Hillis-Steele): the pass for step ``k`` combines each row with
    the one ``k`` rows above it unless a segment starts between them, so a
    row's result combines only rows of its own segment and its rounding grows
    with the segment, not with everything before it. Each pass is one
    elementwise fusion over the rows; ``empty`` is ``op``'s identity.
    """
    n = x.shape[0]
    k = 1
    while k < n:
        up = jnp.pad(x[:-k], ((k, 0),) + ((0, 0),) * (x.ndim - 1),
                     constant_values=empty)
        keep = start.reshape(start.shape + (1,) * (x.ndim - 1))
        x = jnp.where(keep, x, op(up, x))
        start = start | jnp.pad(start[:-k], (k, 0), constant_values=True)
        k *= 2
    return x


def aggregate(t: Table, key: str, aggs: Mapping[str, Tuple[str, str]],
              num_groups: int) -> Table:
    """Group by ``key``; ``aggs`` maps out_name -> (kind, in_column).

    kind in {sum, mean, count, min, max}. Output capacity = ``num_groups``
    (static upper bound on distinct keys; the smallest ``num_groups`` keys
    are kept, rows of larger keys are dropped). Groups come out ascending by
    key, padded with ``_INT_SENTINEL`` keys and invalid rows. The group key
    is emitted under its original name.

    One sort of the masked keys carries the 1-D value columns (wider columns
    follow a carried row index); a group's rows are then contiguous, so each
    result is a segmented scan read at the group's last row, found by a
    ``num_groups``-sized search. No pass scatters or gathers over the rows.
    """
    for kind, _ in aggs.values():
        if kind not in _AGG_KINDS:
            raise ValueError(f"unknown agg kind {kind}")
    n = t.capacity
    km = jnp.where(t.valid, t[key].astype(jnp.int32), _INT_SENTINEL)
    in_cols = sorted({c for kind, c in aggs.values() if kind != "count"})
    flat = [c for c in in_cols if t[c].ndim == 1]
    wide = [c for c in in_cols if t[c].ndim > 1]
    operands = [km] + [jnp.where(t.valid, t[c].astype(jnp.float32), 0.0)
                       for c in flat]
    if wide:
        operands.append(jnp.arange(n, dtype=jnp.int32))
    out = jax.lax.sort(tuple(operands), num_keys=1, is_stable=False)
    s = out[0]
    xs = dict(zip(flat, out[1:]))
    for c in wide:
        xs[c] = t[c].astype(jnp.float32)[out[-1]]

    live = s != _INT_SENTINEL  # invalid rows sort last
    start = jnp.concatenate([jnp.array([True]), s[1:] != s[:-1]]) & live
    # dead rows take the sentinel id, above every slot even where num_groups > n
    gid = jnp.where(live, jnp.cumsum(start.astype(jnp.int32)) - 1,
                    _INT_SENTINEL)
    ng = jnp.sum(start.astype(jnp.int32))
    # last row of each dense id in sorted order (-1 where no row is live)
    last = jnp.searchsorted(gid, jnp.arange(num_groups, dtype=jnp.int32),
                            side="right") - 1
    at = jnp.clip(last, 0, n - 1)
    has = jnp.arange(num_groups) < jnp.minimum(ng, num_groups)
    counts = jnp.diff(last, prepend=-1).astype(jnp.float32)
    cols: Dict[str, jax.Array] = {key: jnp.where(has, s[at], _INT_SENTINEL)}
    for out_name, (kind, in_col) in aggs.items():
        if kind == "count":
            cols[out_name] = counts
            continue
        op, empty = _AGG_SCAN[kind]
        r = _segmented_scan(xs[in_col], start, op, empty)[at]
        grow = (-1,) + (1,) * (r.ndim - 1)
        r = jnp.where(has.reshape(grow), r, empty)
        if kind == "mean":
            r = r / jnp.maximum(counts, 1.0).reshape(grow)
        cols[out_name] = r
    return Table(columns=cols, valid=has)


# ---------------------------------------------------------------------------
# set ops
# ---------------------------------------------------------------------------

def union_all(a: Table, b: Table) -> Table:
    if set(a.columns) != set(b.columns):
        raise ValueError("union_all requires identical schemas")
    cols = {k: jnp.concatenate([a.columns[k], b.columns[k]], axis=0) for k in a.columns}
    return Table(columns=cols, valid=jnp.concatenate([a.valid, b.valid]))
