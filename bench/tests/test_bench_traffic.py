"""The traffic: a fixed multiset of sizes and gaps per mix, shuffled by the
seed, at the mix's rate."""

from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic
from bench.catalog import Catalog
from bench.lake import rng
from bench.lakes import webtable
from bench.traffic import columns
from bench.tests.cpu_run import tiny
from bench.tests.test_bench_lakes import WEB

CAT = Catalog()
# each mix a cell names, with the configuration of one such cell
MIXES_IN_USE = {cell["traffic"]: cell["config"] for cell in CAT.spec["workloads"]}

MIXES = Path(__file__).resolve().parents[1] / "traffic"


@pytest.fixture(scope="module")
def web():
    return webtable.generate(WEB, 1)


def _mix(name: str, **over) -> dict:
    mix = json.loads((MIXES / f"{name}.json").read_text())
    mix.update(over)
    return mix


def test_arrivals_are_poisson_at_the_rate():
    n, rate = 400, 0.8
    due = traffic.arrivals(rate, n, n / rate, rng(5, 3))
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert np.all(gaps > 0) and due[-1] == pytest.approx(n / rate)
    assert gaps.mean() == pytest.approx(1 / rate, rel=1e-6)
    # exponential: the standard deviation equals the mean
    assert gaps.std() == pytest.approx(1 / rate, rel=0.15)
    other = traffic.arrivals(rate, n, n / rate, rng(6, 3))
    assert sorted(np.diff(np.concatenate([[0.0], other]))) == pytest.approx(sorted(gaps))
    assert not np.allclose(other, due)


@pytest.mark.parametrize("rate,seconds,n", [(0.4, 51, 20), (1.1, 51, 56), (0.01, 10, 1)])
def test_request_count(rate, seconds, n):
    assert traffic.request_count(rate, seconds) == n


@pytest.mark.parametrize("mix", sorted(MIXES_IN_USE))
def test_every_mix_is_fixed_by_its_seeds(mix):
    """At its generator's TINY size, on its cell's lake: the run seed
    reorders the rows of the same requests at the same due times."""
    m = CAT.traffic(mix)
    gen = CAT.module("traffic", m["generator"])
    m.update(tiny(gen))
    lake_cfg = CAT.config(MIXES_IN_USE[mix])["lake"]
    lake_gen = CAT.module("lakes", lake_cfg["generator"])
    lake = lake_gen.generate({**lake_cfg["params"], **tiny(lake_gen)}, lake_cfg["seed"])
    a, b, c = (traffic.generate(m, gen.query, lake, seed, 4.0) for seed in (9, 9, 10))
    assert len(a) == traffic.request_count(m["rate"], 4.0)
    assert all(np.array_equal(x.key, y.key) and x.due == y.due for x, y in zip(a, b))
    as_rows = lambda r: sorted(map(tuple, r.key.tolist()))  # noqa: E731
    assert [as_rows(x) for x in a] == [as_rows(y) for y in c]
    assert [(x.due, x.key_width) for x in a] == [(y.due, y.key_width) for y in c]
    assert all(0 < r.key_width <= r.key.shape[1] and r.n_rows > 0 for r in a)


@pytest.mark.parametrize(
    "mix,gen", [(name, columns) for name in sorted(MIXES_IN_USE) if CAT.traffic(name)["generator"] == "columns"]
)
def test_column_queries(web, mix, gen):
    m = _mix(mix, rate=2.0)
    a = traffic.generate(m, gen.query, web, 9, 9.0)
    b = traffic.generate(m, gen.query, web, 9, 9.0)
    c = traffic.generate(m, gen.query, web, 10, 9.0)
    assert len(a) == 18
    assert all(np.array_equal(x.key, y.key) and x.due == y.due for x, y in zip(a, b))
    # another run seed: the same requests at the same times, rows reordered
    as_rows = lambda r: sorted(map(tuple, r.key.tolist()))  # noqa: E731
    assert [as_rows(x) for x in a] == [as_rows(y) for y in c]
    assert [x.due for x in a] == [y.due for y in c]
    assert any(not np.array_equal(x.key, y.key) for x, y in zip(a, c))
    # another mix seed: other requests
    other = traffic.generate(dict(m, seed=m["seed"] + 1), gen.query, web, 9, 9.0)
    assert [as_rows(x) for x in other] != [as_rows(x) for x in a]
    for r in a:
        assert r.key_width in m["key_width"] and 1 <= r.n_rows <= max(m["rows"])
        # no key repeats a value inside itself
        k = r.key[:, : r.key_width]
        assert all(len(set(row)) == r.key_width for row in k.tolist())
    assert sorted(r.due for r in a) == [r.due for r in a]


def test_a_longer_run_offers_a_superset(web):
    m = _mix("fp-nary", rate=2.0)
    short = traffic.generate(m, columns.query, web, 5, 4.0)
    long = traffic.generate(m, columns.query, web, 5, 9.0)
    rows = lambda r: tuple(sorted(map(tuple, r.key.tolist())))  # noqa: E731
    assert len(short) == 8 and {rows(r) for r in short} <= {rows(r) for r in long}
