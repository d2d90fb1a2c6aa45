"""Table: an ordered collection of equal-length columns.

Equivalent of `cudf::table_view` handles crossing the reference's JNI surface
(SURVEY.md §1: L5→L4 passes table handles; e.g. Hash.java:40-58 hashes a
table's column set). A Table is a pytree, so whole tables are jit/shard_map
arguments.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Sequence, Tuple

import jax
import numpy as np

from .column import Column


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Table:
    columns: Tuple[Column, ...]
    names: Tuple[str, ...]

    def __init__(self, columns: Sequence[Column], names: Sequence[str] = None,
                 ordered_by: Sequence[str] = ()):
        columns = tuple(columns)
        if names is None:
            names = tuple(f"c{i}" for i in range(len(columns)))
        assert len(names) == len(columns), (
            f"{len(names)} names for {len(columns)} columns — a mismatched "
            "binding silently shifts every name-based lookup")
        if len(columns) > 1:
            n0 = columns[0].length
            for c in columns[1:]:
                assert c.length == n0, "all columns must have equal length"
        object.__setattr__(self, "columns", columns)
        object.__setattr__(self, "names", tuple(names))
        # the leading columns the rows are known to lie sorted by
        # (ascending, nulls first, as ops/sort.py orders key operands),
        # where the operator that made the table says so: the sorted
        # group-by and the window do, and a window over such a child does
        # not sort again. A statement about THIS object, not part of the
        # pytree: whatever rebuilds the table drops it
        object.__setattr__(self, "ordered_by", tuple(ordered_by))

    def tree_flatten(self):
        return (self.columns,), (self.names,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        (columns,) = leaves
        (names,) = aux
        return cls(columns, names)

    # ---- accessors --------------------------------------------------------------
    @property
    def num_rows(self) -> int:
        return self.columns[0].length if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def __len__(self) -> int:
        return self.num_rows

    def __getitem__(self, key) -> Column:
        if isinstance(key, int):
            return self.columns[key]
        return self.columns[self.names.index(key)]

    def __iter__(self) -> Iterator[Column]:
        return iter(self.columns)

    def column_dict(self) -> Dict[str, Column]:
        return dict(zip(self.names, self.columns))

    def select(self, names: Sequence[str]) -> "Table":
        return Table([self[n] for n in names], names)

    def with_column(self, name: str, col: Column) -> "Table":
        if name in self.names:
            i = self.names.index(name)
            cols = list(self.columns)
            cols[i] = col
            return Table(cols, self.names)
        return Table(list(self.columns) + [col], list(self.names) + [name])

    # ---- host interop -----------------------------------------------------------
    @staticmethod
    def from_pydict(data: Dict[str, Column]) -> "Table":
        return Table(list(data.values()), list(data.keys()))

    def to_pydict(self) -> Dict[str, List]:
        return {n: c.to_pylist() for n, c in zip(self.names, self.columns)}
