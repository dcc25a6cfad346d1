"""Queries keyed on a composite key of a schema lake.

The mix names its keys under ``keys``: each a relation and its key
columns.  A request of size ``{"rows": n, "key": name}`` draws ``n``
distinct rows from one table of that relation (one extract, chosen from
the mix's seed among those with at least ``n`` rows) and carries the key
columns only, so its keys are the relation's own key values: every one
joins the extract it came from, and its values collide with equal small
integers all over the lake.
"""

from __future__ import annotations

import numpy as np

from bench.lake import Lake

# the mix parameters that cut a run to a size a CPU test can hold
TINY = {"rate": 3.0, "rows": [10, 40]}


def query(lake: Lake, mix: dict, size: dict, rng: np.random.Generator):
    key = mix["keys"][size["key"]]
    n = size["rows"]
    cols = [lake.columns[key["relation"]].index(name) for name in key["columns"]]
    tables = [
        t for t, rel in enumerate(lake.relation)
        if rel == key["relation"] and lake.tables[t].shape[0] >= n
    ]
    table = lake.tables[tables[int(rng.integers(len(tables)))]]
    rows = rng.choice(table.shape[0], n, replace=False)
    return table[rows][:, cols], len(cols)
