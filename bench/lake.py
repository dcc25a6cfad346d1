"""A generated lake: tables of vocabulary ids plus the vocabulary itself.

Every lake generator (``bench/lakes/<name>.py``) returns a ``Lake``.  The
benchmark keeps the lake in this integer form for its own use (traffic
sampling, the reference) and hands the system under test plain string
tables (``to_corpus``), so the reference never reads anything the program
built.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one named stream of a run's seed.  Any whole number
    is a seed, beyond 32 bits and below zero too."""
    return np.random.default_rng([seed % (1 << 64), stream])


@dataclasses.dataclass
class Lake:
    tables: list[np.ndarray]  # int32[n_rows, n_cols] vocabulary ids per table
    vocab: np.ndarray  # object[n_values] distinct strings
    relation: list[str] = dataclasses.field(default_factory=list)  # per table
    columns: dict[str, list[str]] = dataclasses.field(default_factory=dict)
    # ^ column names per relation (empty for lakes without a schema)

    def __post_init__(self):
        self.n_rows = np.array([t.shape[0] for t in self.tables], dtype=np.int64)
        self.n_cols = np.array([t.shape[1] for t in self.tables], dtype=np.int64)
        self.row_base = np.zeros(len(self.tables) + 1, dtype=np.int64)
        np.cumsum(self.n_rows, out=self.row_base[1:])
        if not self.relation:
            self.relation = ["table"] * len(self.tables)

    @property
    def total_rows(self) -> int:
        return int(self.row_base[-1])

    @property
    def total_cells(self) -> int:
        return int((self.n_rows * self.n_cols).sum())

    def cell_matrix(self) -> np.ndarray:
        """int32[total_rows, max_cols] ids, -1 where a table is narrower."""
        width = int(self.n_cols.max()) if self.tables else 1
        out = np.full((self.total_rows, width), -1, dtype=np.int32)
        for t, arr in enumerate(self.tables):
            lo = int(self.row_base[t])
            out[lo : lo + arr.shape[0], : arr.shape[1]] = arr
        return out

    def row_table(self) -> np.ndarray:
        """int32[total_rows] table id of each lake row."""
        return np.repeat(
            np.arange(len(self.tables), dtype=np.int32), self.n_rows
        )

    def strings(self, ids: np.ndarray) -> list:
        """Nested lists of the strings behind an id array."""
        return self.vocab[ids].tolist()

    def to_corpus(self):
        """The lake as the program's ``Corpus`` of string tables."""
        from repro.core.corpus import Corpus, Table

        return Corpus(
            [Table(table_id=t, cells=self.strings(arr)) for t, arr in enumerate(self.tables)]
        )

    def shuffled(self, rng: np.random.Generator) -> "Lake":
        """The same tables in another order, each with its rows in another
        order: every table, row and value is kept, each table with its
        relation, so posting-list lengths, candidate tables and join sizes
        stay as they were, while table ids and row offsets follow ``rng``."""
        order = rng.permutation(len(self.tables)).tolist()
        tables = [self.tables[t][rng.permutation(self.tables[t].shape[0])] for t in order]
        relation = [self.relation[t] for t in order]
        return Lake(tables=tables, vocab=self.vocab, relation=relation, columns=self.columns)


def factorize(columns: list[list[np.ndarray]]) -> tuple[list[np.ndarray], np.ndarray]:
    """Map string columns of many tables onto one shared vocabulary.

    ``columns[t]`` is table ``t``'s list of equal-length string arrays.
    Returns each table as an int32 id matrix and the vocabulary.  Equal
    strings get equal ids across tables and columns, which is what makes
    small integers of unrelated columns collide.
    """
    ids: dict[str, int] = {}
    tables = []
    for cols in columns:
        mat = np.empty((len(cols[0]), len(cols)), dtype=np.int32)
        for c, col in enumerate(cols):
            mat[:, c] = [ids.setdefault(v, len(ids)) for v in col.tolist()]
        tables.append(mat)
    vocab = np.empty(len(ids), dtype=object)
    vocab[:] = list(ids)
    return tables, vocab
