"""The lake generators: deterministic per seed, and true to their sources."""

from __future__ import annotations

import importlib

import numpy as np
import pytest

from bench.catalog import Catalog
from bench.lake import Lake, rng
from bench.lakes import webtable
from bench.tests.cpu_run import tiny

WEB = dict(
    n_tables=400, shape_seed=0, rows_lo=5, rows_hi=60, cols_lo=2, cols_hi=24,
    width_alpha=1.6, avg_pl_length=12.0, zipf_a=1.8, head_frac=0.2,
)


@pytest.fixture(scope="module")
def web():
    return webtable.generate(WEB, 3_000_000_019)


def _same(a, b) -> bool:
    return (
        len(a.tables) == len(b.tables)
        and all(np.array_equal(x, y) for x, y in zip(a.tables, b.tables))
        and list(a.vocab) == list(b.vocab)
    )


def _generators() -> list[tuple]:
    """(module, parameters) of each lake generator that a configuration of
    ``BENCHMARK.json`` names: that configuration's, cut to the generator's
    TINY size."""
    cat, out = Catalog(), {}
    for entry in cat.spec["configs"]:
        lake = cat.config(entry["name"])["lake"]
        if lake["generator"] not in out:
            # imported by package name, so every test worker gives it the same id
            gen = importlib.import_module(f"bench.lakes.{lake['generator']}")
            out[lake["generator"]] = (gen, {**lake["params"], **tiny(gen)})
    return list(out.values())


@pytest.mark.parametrize("gen,params", _generators())
def test_same_seed_same_lake(gen, params):
    assert _same(gen.generate(params, 7), gen.generate(params, 7))
    assert not _same(gen.generate(params, 7), gen.generate(params, 8))


def test_webtable_shapes_follow_the_seed_only_in_order(web):
    other = webtable.generate(WEB, 11)
    shape = lambda lake: sorted(t.shape for t in lake.tables)  # noqa: E731
    assert shape(web) == shape(other)
    assert [t.shape for t in web.tables] != [t.shape for t in other.tables]


def test_webtable_width_law(web):
    widths = np.array([t.shape[1] for t in web.tables])
    w = np.arange(2, 25)
    p = w ** -1.6 / (w ** -1.6).sum()
    assert widths.min() >= 2 and widths.max() <= 24
    # width 2 is the mode, with about p[0] of the tables
    assert abs((widths == 2).mean() - p[0]) < 0.06
    heights = np.array([t.shape[0] for t in web.tables])
    assert heights.min() >= 5 and heights.max() <= 59


def test_webtable_posting_list_length_and_head(web):
    ids = np.concatenate([t.ravel() for t in web.tables])
    assert len(set(web.vocab.tolist())) == len(web.vocab)
    # the value pool is sized for 12 posting items per value (DWTC, §7.6.4)
    assert 11.0 <= ids.size / len(web.vocab) <= 13.0
    # the Zipf head: value 0 holds about head_frac / zeta(1.8) of the cells
    assert 0.07 < (ids == 0).mean() < 0.14


def test_a_shuffled_lake_keeps_every_row(web):
    """The run's seed lays the lake out: the same tables, rows and values in
    another order, so the work of a request does not change with it."""
    a, b = web.shuffled(rng(5, 5)), web.shuffled(rng(5, 5))
    assert _same(a, b) and not _same(a, web.shuffled(rng(6, 5)))
    rows = lambda lake: sorted(tuple(r) for t in lake.tables for r in t.tolist())  # noqa: E731
    assert rows(a) == rows(web) and a.vocab is web.vocab
    assert sorted(t.shape for t in a.tables) == sorted(t.shape for t in web.tables)
    assert [t.shape for t in a.tables] != [t.shape for t in web.tables]
    # each table keeps its rows as a set; most change their order
    by_set = lambda lake: sorted(sorted(map(tuple, t.tolist())) for t in lake.tables)  # noqa: E731
    assert by_set(a) == by_set(web)


def test_a_shuffled_lake_keeps_each_table_with_its_relation(web):
    """A schema lake keeps its relation names and columns through the
    layout every run draws; a lake without a schema reads ``table``."""
    tables = [np.full((3, 2 + t % 2), t, dtype=np.int32) for t in range(8)]
    columns = {"orders": ["o_orderkey", "o_custkey"], "lineitem": ["l_orderkey", "l_partkey", "l_suppkey"]}
    lake = Lake(tables=tables, vocab=np.arange(8).astype(str).astype(object),
                relation=["orders", "lineitem"] * 4, columns=columns)
    moved = lake.shuffled(rng(5, 5))
    assert [int(t[0, 0]) for t in moved.tables] != list(range(8))
    for table, relation in zip(moved.tables, moved.relation):
        assert relation == lake.relation[int(table[0, 0])]
        assert table.shape[1] == len(moved.columns[relation])
    assert moved.columns == columns
    assert web.shuffled(rng(5, 5)).relation == ["table"] * len(web.tables)
