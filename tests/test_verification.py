"""Phase-B verification: the re-gather of eligible pairs only and exact
verification on lake value ids.

Exact verification compares ``Corpus.cell_value_ids`` rows with the key's
value ids; it must count the same true and false pairs, and pick the same
joinability and mapping (ties to the largest), as ``discovery._verify_pair``
on the strings, which the sequential engine still uses.  The lazy re-gather
tests only each table's eligible (row, key) pairs; the mask it hands to
verification must equal ``subsume_np`` over all keys masked by eligibility.
"""

import asyncio
import dataclasses
from collections import defaultdict

import numpy as np
import pytest

from bench.lakes import tpch
from conftest import indexes_at_widths, mixed_query_lake
from repro import telemetry
from repro.core import batched as B
from repro.core import discovery, xash
from repro.core.corpus import Corpus, Table
from repro.core.discovery import DiscoveryStats
from repro.core.index import MateIndex
from repro.core.session import DiscoveryConfig, MateSession
from repro.kernels import ops
from repro.serve.clock import ManualClock
from repro.serve.engine import AsyncDiscoveryEngine


def _on_strings(index, plan, rows, hits):
    """Verification as the sequential engine does it: ``_verify_pair`` on
    the strings, one pair at a time.  Returns ((J, mapping), stats)."""
    st = DiscoveryStats()
    per_mapping = defaultdict(set)
    for r, kid in zip(*np.nonzero(hits)):
        key = plan.distinct_keys[int(kid)]
        found = discovery._verify_pair(key, index.corpus.row_values(int(rows[r])))
        if found:
            st.verified_tp += 1
            for m in found:
                per_mapping[m].add(key)
        else:
            st.verified_fp += 1
    if not per_mapping:
        return (0, None), st
    mapping, keys = max(per_mapping.items(), key=lambda kv: (len(kv[1]), kv[0]))
    return (len(keys), mapping), st


def _on_ids(index, plan, rows, hits):
    plan = dataclasses.replace(plan, stats=DiscoveryStats())
    out = B._calculate_j(index, plan, rows, hits)
    return out, plan.stats


def _same_verification(index, plan, rows, hits):
    (j, m), st = _on_ids(index, plan, rows, hits)
    (j_ref, m_ref), st_ref = _on_strings(index, plan, rows, hits)
    assert (j, m) == (j_ref, m_ref)
    assert m is None or all(type(c) is int for c in m)
    assert (st.verified_tp, st.verified_fp) == (st_ref.verified_tp, st_ref.verified_fp)
    return j, m, st


def _plan(corpus, keys):
    index = MateIndex(corpus, cfg=xash.XashConfig(bits=128))
    query = Table(-1, [list(k) for k in keys])
    return index, B.plan_query(index, query, list(range(len(keys[0]))))


EDGE_TABLES = [
    # a value in two columns of one row: several mappings per pair
    [["a", "b", "a"], ["b", "a", "c"], ["a", "a", "b"], ["c", "b", "b"]],
    # a key that repeats a value, once and twice in the row
    [["x", "x", "y"], ["x", "y", "z"], ["y", "x", "y"]],
    # rows narrower than the key
    [["a"], ["x"]],
    [["b", "a"], ["a", "zz"]],
]
EDGE_KEYS = [
    ("a", "b"), ("b", "a"), ("a", "a"), ("x", "x"), ("x", "y"), ("y", "y"),
    ("a", "not-in-lake"), ("not-in-lake", "x"), ("b", "b"),
]


@pytest.mark.parametrize("t", range(len(EDGE_TABLES)))
def test_value_ids_match_strings_on_edge_rows(t):
    corpus = Corpus([Table(i, cells) for i, cells in enumerate(EDGE_TABLES)])
    index, plan = _plan(corpus, EDGE_KEYS)
    # the key ids, with -1 for the value the lake lacks
    missing = [k for k, key in enumerate(plan.distinct_keys) if "not-in-lake" in key]
    assert missing and all((plan.key_ids[k] == -1).sum() == 1 for k in missing)
    lo, hi = int(corpus.row_base[t]), int(corpus.row_base[t + 1])
    rows = np.arange(lo, hi)
    hits = np.ones((hi - lo, len(plan.distinct_keys)), dtype=bool)
    j, m, st = _same_verification(index, plan, rows, hits)
    assert st.verified_tp + st.verified_fp == hits.size
    if t == 0:  # ("a", "b") and ("b", "a") each map two ways in some row
        assert st.verified_tp >= 6 and j >= 2
    if t == 2:  # one column cannot hold a width-2 key
        assert (j, m, st.verified_tp) == (0, None, 0)


def test_mapping_ties_go_to_the_largest_mapping():
    corpus = Corpus([Table(0, [["p", "q", "p", "q"]])])
    index, plan = _plan(corpus, [("p", "q")])
    j, m, _ = _same_verification(index, plan, np.array([0]), np.ones((1, 1), dtype=bool))
    # (0, 1), (0, 3), (2, 1), (2, 3) each count the one key: the largest wins
    assert (j, m) == (1, (2, 3))


@pytest.mark.parametrize("seed", range(4))
def test_value_ids_match_strings_on_random_rows(seed):
    """Small alphabets put key values in several columns and keys repeat
    values; a few key values are not in the lake at all."""
    rng = np.random.default_rng(seed)
    alphabet = np.array(["1", "2", "3", "4", "5"])
    tables = [
        Table(t, alphabet[rng.integers(0, 5, (int(rng.integers(1, 7)), int(rng.integers(1, 6))))].tolist())
        for t in range(12)
    ]
    corpus = Corpus(tables)
    width = 2 + seed % 2
    keys = list(dict.fromkeys(
        tuple(alphabet[rng.integers(0, 5, width)].tolist()) for _ in range(30)
    )) + [("6",) * width]
    index, plan = _plan(corpus, keys)
    for t in range(len(tables)):
        lo, hi = int(corpus.row_base[t]), int(corpus.row_base[t + 1])
        hits = rng.random((hi - lo, len(plan.distinct_keys))) < 0.7
        _same_verification(index, plan, np.arange(lo, hi), hits)


@pytest.mark.parametrize("gathered", [False, True])
def test_regather_pairs_equal_the_full_regather(gathered, monkeypatch):
    """Table by table, the pairs the lazy re-gather hands to verification
    are ``np.nonzero`` of ``subsume_np`` over all keys masked by
    eligibility, in its order, and ``regather_pairs`` counts the eligible
    pairs alone."""
    corpus, queries = mixed_query_lake(n_tables=60, n_rows=30)
    index = MateIndex(corpus, cfg=xash.XashConfig(bits=128))
    query, q_cols = queries[0]
    plan = B.plan_query(index, query, q_cols)
    block = plan.block
    n_tables = block.n_tables
    row_sk = index.superkey_of_rows(block.rows)
    seen = []
    calc = B._calculate_j

    def spy(index_, plan_, rows, hits):
        seen.append(np.nonzero(hits))
        return calc(index_, plan_, rows, hits)

    monkeypatch.setattr(B, "_calculate_j", spy)
    # every table above the bound: each is re-gathered, in block order
    B._score_tables(
        index, plan, B._TopK(n_tables + 1), None, np.ones(n_tables, dtype=np.int32),
        row_sk=None if gathered else row_sk,
    )
    assert len(seen) == n_tables
    for t, (rs, ks) in enumerate(seen):
        lo, hi = int(block.table_ptr[t]), int(block.table_ptr[t + 1])
        want = np.nonzero(
            ops.subsume_np(row_sk[lo:hi], plan.q_sk) & plan.elig[lo:hi].dense()
        )
        assert np.array_equal(rs, want[0]) and np.array_equal(ks, want[1])
        # the eligible pairs themselves, in the order of the dense block
        got = B._eligible_pairs(plan, block.value_idx[lo:hi])
        want = np.nonzero(plan.elig[lo:hi].dense())
        assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    assert plan.stats.regather_pairs == int(plan.elig.dense().sum())
    assert plan.stats.regather_pairs < plan.elig.dense().size


@pytest.mark.parametrize("n_pairs", [3_000, 3 * ops._PAIRS_BY_LANE])
@pytest.mark.parametrize("bits", [128, 256, 512])
def test_pairwise_subsumption_equals_the_dense_test(bits, n_pairs):
    """Few pairs take one pass over the lanes; many go lane by lane first."""
    rng = np.random.default_rng(bits + n_pairs)
    lanes = bits // 32
    # sparse keys (a sixteenth of a row's bits): a pair with another row
    # passes each lane by chance, so pairs fail at every lane
    row_sk = rng.integers(0, 2**32, (50, lanes), dtype=np.uint32)
    sparse = np.bitwise_and.reduce(rng.integers(0, 2**32, (4, 20, lanes), dtype=np.uint32))
    q_sk = row_sk[rng.integers(0, 50, 20)] & sparse
    rows, keys = rng.integers(0, 50, n_pairs), rng.integers(0, 20, n_pairs)
    got = ops.subsume_pairs_np(row_sk, q_sk, rows, keys)
    assert np.array_equal(got, ops.subsume_np(row_sk, q_sk)[rows, keys])
    assert got.any() and not got.all()


def _tpch_queries(lake, rows, seed):
    rng = np.random.default_rng(seed)
    li = [t for t, rel in enumerate(lake.relation) if rel == "lineitem"]
    cols = lake.columns["lineitem"]
    out = []
    for names in (("l_partkey", "l_suppkey"), ("l_orderkey", "l_linenumber")):
        table = lake.tables[li[int(rng.integers(len(li)))]]
        pick = rng.choice(table.shape[0], min(rows, table.shape[0]), replace=False)
        key = table[pick][:, [cols.index(n) for n in names]]
        out.append((Table(-1, lake.strings(key)), [0, 1]))
    return out


def _tiny_tpch():
    lake = tpch.generate({"scale_factor": 0.0005}, 5)
    return lake.to_corpus(), _tpch_queries(lake, 25, 3)


def _web():
    corpus, queries = mixed_query_lake(n_tables=80, n_rows=25, n_queries=3)
    return corpus, queries


@pytest.mark.parametrize("lake,bits", [("tpch", 256), ("web", 128), ("web", 512)])
def test_batched_equals_sequential(lake, bits):
    """Top-k of the lazy paths (fused and gather-fused launches: eligible-
    pair re-gather, value-id verification) equal Algorithm 1's on the
    strings; the verified pair counts equal those of the host-hits path,
    which verifies the launch's own match matrix."""
    corpus, queries = _tiny_tpch() if lake == "tpch" else _web()
    (index,) = indexes_at_widths(corpus, widths=(bits,)).values()
    host = B.discover_many(index, queries, k=5, backend="numpy")
    for backend in ("fused", "fused-gather"):
        lazy = B.discover_many(index, queries, k=5, backend=backend)
        for (q, qc), (got, st), (want_host, st_host) in zip(queries, lazy, host):
            want, _ = discovery.discover(index, q, qc, k=5)
            as_tuples = lambda es: [(e.table_id, e.joinability, e.mapping) for e in es]  # noqa: E731
            assert as_tuples(got) == as_tuples(want) == as_tuples(want_host)
            assert (st.verified_tp, st.verified_fp) == (st_host.verified_tp, st_host.verified_fp)
            assert 0 < st.regather_pairs <= st.filter_checks
            assert st_host.regather_pairs == 0  # its hits came back whole


def test_regather_pairs_is_counted_and_absorbed():
    corpus, queries = mixed_query_lake(n_tables=60, n_rows=30)
    session = MateSession.build(
        corpus, DiscoveryConfig(bits=128, k=3, window=1, backend="fused-gather")
    )
    query, q_cols = queries[0]

    async def serve():
        engine = AsyncDiscoveryEngine(session=session, clock=ManualClock())
        async with engine:
            return await engine.discover_async(query, q_cols)

    asyncio.run(serve())  # compile first
    before = session.stats.regather_pairs
    telemetry.enable()
    try:
        req = asyncio.run(serve())
    finally:
        records = telemetry.disable()
    st = req.stats
    assert 0 < st.regather_pairs <= st.filter_checks
    assert session.stats.regather_pairs - before == st.regather_pairs
    (tables,) = [s.attrs for s in records if s.name == "score.tables"]
    assert tables["regather_pairs"] == st.regather_pairs
    assert [s.name for s in records].count("plan.key_ids") == 1
