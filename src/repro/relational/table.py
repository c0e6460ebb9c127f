"""Static-shape columnar Table.

A Table is a pytree: ``columns`` maps name -> jnp array whose leading axis is
the row capacity; ``valid`` is a bool[capacity] mask. Invalid rows carry
garbage values and must never influence query results — every operator and
every test is mask-aware.

Columns may be scalar (shape [N]) or vector (shape [N, d]) — vector columns
are the paper's ``V: vec in R^d`` feature-vector columns (Sec. III-A).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.relational.oracle import canonical


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Table:
    columns: Dict[str, jax.Array]
    valid: jax.Array  # bool[capacity]

    # -- pytree protocol -------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[n] for n in names) + (self.valid,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(columns=dict(zip(names, children[:-1])), valid=children[-1])

    # -- construction ----------------------------------------------------
    @classmethod
    def from_columns(cls, columns: Mapping[str, jax.Array], valid=None) -> "Table":
        cols = {k: jnp.asarray(v) for k, v in columns.items()}
        n = next(iter(cols.values())).shape[0]
        for k, v in cols.items():
            if v.shape[0] != n:
                raise ValueError(f"column {k} has {v.shape[0]} rows, expected {n}")
        if valid is None:
            valid = jnp.ones((n,), dtype=bool)
        return cls(columns=cols, valid=jnp.asarray(valid, dtype=bool))

    @classmethod
    def empty_like(cls, other: "Table", capacity: int) -> "Table":
        cols = {
            k: jnp.zeros((capacity,) + v.shape[1:], v.dtype)
            for k, v in other.columns.items()
        }
        return cls(columns=cols, valid=jnp.zeros((capacity,), dtype=bool))

    # -- accessors --------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    @property
    def names(self):
        return tuple(sorted(self.columns))

    def __getitem__(self, name: str) -> jax.Array:
        return self.columns[name]

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32))

    def with_columns(self, new: Mapping[str, jax.Array]) -> "Table":
        cols = dict(self.columns)
        cols.update(new)
        return Table(columns=cols, valid=self.valid)

    def select(self, names) -> "Table":
        return Table(columns={n: self.columns[n] for n in names}, valid=self.valid)

    def rename(self, mapping: Mapping[str, str]) -> "Table":
        cols = {mapping.get(k, k): v for k, v in self.columns.items()}
        return Table(columns=cols, valid=self.valid)

    # -- materialization (host side, for tests / oracles) -----------------
    def to_numpy(self) -> Dict[str, np.ndarray]:
        """Valid rows only, as numpy, in storage order."""
        mask = np.asarray(self.valid)
        return {k: np.asarray(v)[mask] for k, v in self.columns.items()}

    def canonical(self) -> Dict[str, np.ndarray]:
        """Valid rows sorted by a total order over all scalar columns — used
        to compare plan outputs irrespective of row order."""
        return canonical(self.to_numpy())
