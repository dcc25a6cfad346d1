"""Queries whose key is a foreign key: the mix's ``key`` columns of rows
drawn from one table of the mix's ``relation``."""

from __future__ import annotations

import numpy as np

from bench.lake import Lake

TINY = {"rate": 3.0, "rows": [10, 40]}


def query(lake: Lake, mix: dict, size: dict, rng: np.random.Generator):
    cols = [lake.columns[mix["relation"]].index(name) for name in mix["key"]]
    tables = [t for t, rel in enumerate(lake.relation) if rel == mix["relation"]]
    table = lake.tables[tables[int(rng.integers(len(tables)))]]
    return table[rng.integers(0, table.shape[0], size["rows"])][:, cols], len(cols)
