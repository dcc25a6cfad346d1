"""The lake generators: deterministic per seed, and true to their sources."""

from __future__ import annotations

import numpy as np
import pytest

from bench.lake import rng
from bench.lakes import webtable

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


@pytest.mark.parametrize("gen,params", [(webtable, WEB)])
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
