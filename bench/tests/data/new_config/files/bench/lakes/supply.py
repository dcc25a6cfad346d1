"""A two-relation supply lake with a composite foreign key:
lineitem(l_partkey, l_suppkey) references partsupp(ps_partkey, ps_suppkey),
in the manner of TPC-H.  Each relation is cut into ``extracts`` tables of
consecutive rows; every part has ``supps_per_part`` distinct suppliers, and
every line item picks one partsupp row."""

from __future__ import annotations

import numpy as np

from bench.lake import Lake, factorize, rng as seeded

TINY = {"parts": 40, "lines": 240, "extracts": 3}

COLUMNS = {
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty"],
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity"],
}


def generate(params: dict, seed: int) -> Lake:
    rng = seeded(seed, 1)
    per, n_supps = params["supps_per_part"], params["supps"]
    part = np.repeat(np.arange(params["parts"]), per)
    supp = (part + np.tile(np.arange(per), params["parts"]) * (n_supps // per)) % n_supps
    line = rng.integers(0, part.size, params["lines"])
    rows = {
        "partsupp": [part, supp, rng.integers(1, 10_000, part.size)],
        "lineitem": [
            np.sort(rng.integers(0, params["lines"] // 4, params["lines"])),
            part[line], supp[line], rng.integers(1, 51, params["lines"]),
        ],
    }
    prefix = {"ps_partkey": "P", "l_partkey": "P", "ps_suppkey": "S", "l_suppkey": "S", "l_orderkey": "O"}
    columns, relation = [], []
    for rel, cols in rows.items():
        text = [np.array([prefix.get(name, "") + str(v) for v in col.tolist()])
                for name, col in zip(COLUMNS[rel], cols)]
        for part_rows in np.array_split(np.arange(text[0].size), params["extracts"]):
            columns.append([col[part_rows] for col in text])
            relation.append(rel)
    tables, vocab = factorize(columns)
    return Lake(tables=tables, vocab=vocab, relation=relation, columns=COLUMNS)
