"""The TPC-H warehouse lake (``bench/lakes/tpch.py``) at its TINY size: the
key rules of the specification's clause 4.2.3, the extracts it exports, and
the reference's joinability on a foreign-key request, derived from the
schema alone."""

from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

from bench import traffic
from bench.catalog import Catalog
from bench.lake import rng
from bench.lakes import tpch
from bench.reference import Reference
from bench.tests.cpu_run import tiny

CAT = Catalog()
CONFIG = CAT.config("tpch-lake")
PARAMS = {**CONFIG["lake"]["params"], **tiny(tpch)}
SEED = CONFIG["lake"]["seed"]


@pytest.fixture(scope="module")
def rel():
    return tpch.tables(PARAMS, SEED)


@pytest.fixture(scope="module")
def lake():
    return tpch.generate(PARAMS, SEED)


def test_cardinalities_follow_the_scale_factor(rel):
    sf = PARAMS["scale_factor"]
    want = {"part": 200_000, "supplier": 10_000, "partsupp": 800_000,
            "customer": 150_000, "orders": 1_500_000}
    for name, per_sf in want.items():
        assert rel[name][tpch.COLUMNS[name][0]].size == round(sf * per_sf), name
    n_li = rel["lineitem"]["l_orderkey"].size
    assert 3 * rel["orders"]["o_orderkey"].size < n_li < 5 * rel["orders"]["o_orderkey"].size
    assert rel["nation"]["n_nationkey"].size == 25 and rel["region"]["r_regionkey"].size == 5


def test_partsupp_suppliers_follow_the_formula(rel):
    ps = rel["partsupp"]
    s = rel["supplier"]["s_suppkey"].size
    p = ps["ps_partkey"]
    i = np.tile(np.arange(4), p.size // 4)
    assert np.array_equal(ps["ps_suppkey"], (p + i * (s // 4 + (p - 1) // s)) % s + 1)
    assert np.array_equal(p, np.repeat(np.arange(1, p.size // 4 + 1), 4))
    assert ps["ps_suppkey"].min() >= 1 and ps["ps_suppkey"].max() <= s


def test_every_lineitem_references_a_partsupp_row(rel):
    ps = set(zip(rel["partsupp"]["ps_partkey"].tolist(), rel["partsupp"]["ps_suppkey"].tolist()))
    li = rel["lineitem"]
    assert set(zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist())) <= ps


def test_order_keys_are_sparse_and_line_numbers_run_per_order(rel):
    ok = rel["orders"]["o_orderkey"]
    assert np.all(np.diff(ok) > 0) and np.all((ok - 1) % 32 < 8)
    assert ok[-1] == ((ok.size - 1) // 8) * 32 + (ok.size - 1) % 8 + 1
    li = rel["lineitem"]
    assert set(li["l_orderkey"].tolist()) == set(ok.tolist())
    for key, lines in _group(li["l_orderkey"], li["l_linenumber"]).items():
        assert lines == list(range(1, len(lines) + 1)) and len(lines) <= 7, key
    assert li["l_linenumber"].min() == 1 and li["l_linenumber"].max() == 7


def test_no_customer_key_divisible_by_three_orders(rel):
    cust = rel["orders"]["o_custkey"]
    assert np.all(cust % 3 != 0)
    assert cust.min() >= 1 and cust.max() <= rel["customer"]["c_custkey"].size


def test_values_are_written_as_dbgen_writes_them(lake):
    li = lake.columns["lineitem"]
    table = lake.tables[lake.relation.index("lineitem")]
    row = dict(zip(li, lake.vocab[table[0]].tolist()))
    assert row["l_orderkey"].isdigit() and row["l_linenumber"] in "1234567"
    assert len(row["l_shipdate"]) == 10 and row["l_shipdate"][4] == "-"
    assert row["l_extendedprice"].split(".")[1].isdigit() and len(row["l_extendedprice"].split(".")[1]) == 2
    assert row["l_discount"].startswith("0.") and len(row["l_discount"]) == 4
    assert tpch._money(np.array([-5, 0, 123456])).tolist() == ["-0.05", "0.00", "1234.56"]


def test_extracts_and_their_schema(lake):
    count = Counter(lake.relation)
    assert count["orders"] == count["lineitem"] <= 80
    assert count["part"] == count["partsupp"] == 25
    assert count["customer"] <= 25 and count["supplier"] <= 25
    assert count["nation"] == count["region"] == 1
    assert set(lake.columns) == set(tpch.COLUMNS) == set(count)
    for table, relation in zip(lake.tables, lake.relation):
        assert table.shape[0] > 0 and table.shape[1] == len(lake.columns[relation])
    # an orders extract holds one month; its line items are the lineitem
    # extract of that month
    month = _by_relation(lake, "orders", "o_orderdate", lambda v: {d[:7] for d in v})
    keys = _by_relation(lake, "orders", "o_orderkey", set)
    lines = _by_relation(lake, "lineitem", "l_orderkey", set)
    assert all(len(m) == 1 for m in month)
    assert sorted(map(frozenset, keys)) == sorted(map(frozenset, lines))
    # a partsupp extract holds the parts of one brand, as the part extract does
    parts = _by_relation(lake, "part", "p_partkey", set)
    ps_parts = _by_relation(lake, "partsupp", "ps_partkey", set)
    assert sorted(map(frozenset, parts)) == sorted(map(frozenset, ps_parts))


def test_a_shuffled_lake_keeps_the_schema(lake):
    moved = lake.shuffled(rng(5, 5))
    assert moved.columns == lake.columns
    assert Counter(moved.relation) == Counter(lake.relation)
    rows = lambda lk: Counter((r, tuple(sorted(map(tuple, t.tolist())))) for r, t in zip(lk.relation, lk.tables))  # noqa: E731
    assert rows(moved) == rows(lake)
    assert [t.shape for t in moved.tables] != [t.shape for t in lake.tables]


def test_reference_joins_a_foreign_key_to_its_partsupp_extracts(lake, rel):
    """A ``lineitem_partsupp`` request: each partsupp extract (one brand)
    joins exactly the distinct query keys whose part has that brand, under
    the mapping (ps_partkey, ps_suppkey) -- read from the schema, not from
    the program."""
    mix = CAT.traffic("fk-nary")
    gen = CAT.module("traffic", mix["generator"])
    key, width = gen.query(lake, mix, {"rows": 40, "key": "lineitem_partsupp"}, rng(11, 3))
    assert width == 2 and key.shape == (40, 2)
    distinct = set(map(tuple, lake.vocab[key].tolist()))
    brand_of_key = Counter(rel["part"]["p_brand"][int(p) - 1] for p, _ in distinct)
    truth = Reference(lake).joinability(key)
    checked = 0
    for t, relation in enumerate(lake.relation):
        if relation != "partsupp":
            continue
        (brand,) = {rel["part"]["p_brand"][int(p) - 1] for p in lake.vocab[lake.tables[t][:, 0]].tolist()}
        want = brand_of_key.get(brand, 0)
        if want:
            assert truth[t][0] == want, brand
            if want >= 2:
                assert truth[t][1] == (0, 1), brand
            checked += 1
    assert checked >= 5


def test_the_mix_draws_distinct_rows_of_one_extract(lake):
    mix = CAT.traffic("fk-nary")
    assert mix["vary"] == ["rows", "key"] and mix["rows"] == [10, 100, 1000]
    gen = CAT.module("traffic", mix["generator"])
    reqs = traffic.generate({**mix, **tiny(gen)}, gen.query, lake, 9, 4.0)
    cols = {name: [lake.columns["lineitem"].index(c) for c in k["columns"]] for name, k in mix["keys"].items()}
    extracts = [t for t, r in zip(lake.tables, lake.relation) if r == "lineitem"]
    for r in reqs:
        assert r.key_width == 2 and r.key.shape[1] == 2
        rows = [tuple(k) for k in r.key.tolist()]
        homes = {
            name for name, c in cols.items() for t in extracts
            if set(rows) <= set(map(tuple, t[:, c].tolist()))
        }
        assert homes
        if "lineitem_pk" in homes:  # a primary key: rows drawn without replacement
            assert len(set(rows)) == len(rows)
    assert len({r.n_rows for r in reqs}) == 2


def _group(keys: np.ndarray, values: np.ndarray) -> dict:
    out: dict = {}
    for k, v in zip(keys.tolist(), values.tolist()):
        out.setdefault(k, []).append(v)
    return out


def _by_relation(lake, relation: str, column: str, fold) -> list:
    c = lake.columns[relation].index(column)
    return [fold(lake.vocab[t[:, c]].tolist()) for t, r in zip(lake.tables, lake.relation) if r == relation]
