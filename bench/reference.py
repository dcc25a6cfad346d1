"""The plain reference: exact n-ary joinability of every lake table.

Written from the MATE paper's definition (Eq. 2), independent of the
program: the joinability of table T for query Q on key columns K is the
number of distinct key tuples of Q that appear in some row of T under one
injective mapping of K onto T's columns, maximised over mappings.  Ties
between mappings go to the lexicographically largest mapping, the order
the served entries report.

It reads only the benchmark's own lake (vocabulary ids) and the request's
key ids: for each distinct key, the rows that hold all of its values are
found from the rarest value's row list, and every injective placement of
the key in such a row counts the key for that (table, mapping).
"""

from __future__ import annotations

import itertools

import numpy as np

from bench.lake import Lake

# a mapping is packed into one integer, most significant column first, so
# comparing packed codes compares mapping tuples lexicographically
_BASE = 64


class Reference:
    def __init__(self, lake: Lake):
        self.cells = lake.cell_matrix()
        self.row_table = lake.row_table()
        n_rows, width = self.cells.shape
        flat = self.cells.ravel()
        rows = np.repeat(np.arange(n_rows, dtype=np.int64), width)
        live = flat >= 0
        vals, rows = flat[live].astype(np.int64), rows[live]
        order = np.lexsort((rows, vals))
        vals, rows = vals[order], rows[order]
        first = np.ones(vals.size, dtype=bool)
        first[1:] = (vals[1:] != vals[:-1]) | (rows[1:] != rows[:-1])
        vals, self.rows = vals[first], rows[first]
        self.ptr = np.zeros(len(lake.vocab) + 1, dtype=np.int64)
        np.cumsum(np.bincount(vals, minlength=len(lake.vocab)), out=self.ptr[1:])

    def joinability(self, key: np.ndarray) -> dict[int, tuple[int, tuple[int, ...]]]:
        """``{table: (joinability, mapping)}`` for every table with
        joinability above 0; ``key`` is int[n, width] vocabulary ids."""
        keys = np.unique(np.asarray(key, dtype=np.int64), axis=0)
        n, width = keys.shape
        if n == 0:
            return {}
        lens = self.ptr[keys + 1] - self.ptr[keys]
        rare = keys[np.arange(n), lens.argmin(axis=1)]
        counts = lens.min(axis=1)
        key_of = np.repeat(np.arange(n), counts)
        start = np.repeat(self.ptr[rare] - np.cumsum(counts) + counts, counts)
        rows = self.rows[start + np.arange(key_of.size)]
        cells = self.cells[rows]
        at = [cells == keys[key_of, i][:, None] for i in range(width)]
        hit = np.logical_and.reduce([a.any(axis=1) for a in at])
        key_of, rows, at = key_of[hit], rows[hit], [a[hit] for a in at]

        single = np.logical_and.reduce([a.sum(axis=1) == 1 for a in at])
        pos = np.stack([a.argmax(axis=1) for a in at], axis=1)
        injective = np.ones(single.size, dtype=bool)
        for i in range(width):
            for j in range(i + 1, width):
                injective &= pos[:, i] != pos[:, j]
        keep = single & injective
        tables = [self.row_table[rows[keep]].astype(np.int64)]
        codes = [_pack(pos[keep])]
        owners = [key_of[keep]]
        for p in np.flatnonzero(~single).tolist():  # a value repeats in the row
            per = [np.flatnonzero(a[p]).tolist() for a in at]
            for m in itertools.product(*per):
                if len(set(m)) == width:
                    tables.append(np.array([self.row_table[rows[p]]], dtype=np.int64))
                    codes.append(_pack(np.array([m])))
                    owners.append(np.array([key_of[p]]))
        table, code, owner = (np.concatenate(x) for x in (tables, codes, owners))
        triples = np.unique(np.stack([table, code, owner], axis=1), axis=0)
        pairs, size = np.unique(triples[:, :2], axis=0, return_counts=True)
        out: dict[int, tuple[int, tuple[int, ...]]] = {}
        for (t, c), j in zip(pairs.tolist(), size.tolist()):
            best = out.get(t)
            if best is None or (j, c) > (best[0], best[1]):
                out[t] = (j, c)
        return {t: (j, _unpack(c, width)) for t, (j, c) in out.items()}


def _pack(pos: np.ndarray) -> np.ndarray:
    code = np.zeros(pos.shape[0], dtype=np.int64)
    for i in range(pos.shape[1]):
        code = code * _BASE + pos[:, i]
    return code


def _unpack(code: int, width: int) -> tuple[int, ...]:
    out = []
    for _ in range(width):
        out.append(code % _BASE)
        code //= _BASE
    return tuple(reversed(out))


def check(entries, truth: dict[int, tuple[int, tuple[int, ...]]], k: int) -> str | None:
    """Why the served top-k ``entries`` are wrong, or None when they are k
    (or all, if fewer joinable) distinct tables of maximal joinability,
    each with its exact joinability and mapping."""
    want = sorted((j for j, _ in truth.values()), reverse=True)[:k]
    ids = [e.table_id for e in entries]
    if len(set(ids)) != len(ids):
        return f"duplicate tables {ids}"
    if len(entries) != len(want):
        return f"{len(entries)} entries, want {len(want)}"
    for e in entries:
        if e.table_id not in truth:
            return f"table {e.table_id} is not joinable, served J={e.joinability}"
        j, m = truth[e.table_id]
        if (e.joinability, tuple(e.mapping or ())) != (j, m):
            return (
                f"table {e.table_id}: served J={e.joinability} mapping={e.mapping}, "
                f"reference J={j} mapping={m}"
            )
    got = sorted((e.joinability for e in entries), reverse=True)
    if got != want:
        return f"joinabilities {got}, want the top {want}"
    return None
