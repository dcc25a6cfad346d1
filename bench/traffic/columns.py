"""Queries whose key columns each come from a different lake table.

A vectorised copy of ``repro.data.synthetic.make_mixed_queries``, the
paper's false-positive-heavy regime: every key column holds values of one
random column of one random table, sampled with replacement, so single
values hit many posting lists while whole keys rarely co-occur in a row.
Rows that repeat a value inside their own key are dropped, as there.
"""

from __future__ import annotations

import numpy as np

from bench.lake import Lake

# the mix parameters that cut a run to a size a CPU test can hold
TINY = {"rate": 3.0, "rows": [10, 60]}


def query(lake: Lake, mix: dict, size: dict, rng: np.random.Generator):
    width, n = size["key_width"], size["rows"]
    while True:
        cols = []
        for t in rng.integers(0, len(lake.tables), width).tolist():
            table = lake.tables[t]
            col = int(rng.integers(table.shape[1]))
            cols.append(table[rng.integers(0, table.shape[0], n), col])
        key = np.stack(cols, axis=1)
        distinct = np.ones(n, dtype=bool)
        for i in range(width):
            for j in range(i + 1, width):
                distinct &= key[:, i] != key[:, j]
        if distinct.any():
            return key[distinct], width
