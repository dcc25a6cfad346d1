"""Discovery (Algorithm 1) correctness: vs brute force, engines, baselines."""

import dataclasses

import numpy as np
import pytest

from repro.core import discovery, xash
from repro.core.batched import discover_many
from repro.core.corpus import Corpus, Table
from repro.core.index import MateIndex
from repro.data import synthetic


@pytest.fixture(scope="module")
def lake():
    spec = synthetic.SyntheticSpec(n_tables=150, seed=0)
    corpus = synthetic.make_corpus(spec)
    query, q_cols, expected, corpus = synthetic.make_query_with_ground_truth(corpus)
    index = MateIndex(corpus)
    return corpus, index, query, q_cols, expected


@pytest.fixture(scope="module")
def lake512(lake):
    """Same corpus/query, indexed at 512-bit (16-lane) super keys."""
    corpus, _index, query, q_cols, expected = lake
    index = MateIndex(corpus, cfg=xash.XashConfig(bits=512))
    return corpus, index, query, q_cols, expected


def test_topk_matches_bruteforce_and_ground_truth(lake):
    corpus, index, query, q_cols, expected = lake
    topk, stats = discovery.discover(index, query, q_cols, k=10)
    bf = discovery.topk_bruteforce(corpus, query, q_cols, 10)
    assert [(e.table_id, e.joinability) for e in topk] == bf
    exp_sorted = sorted(expected.items(), key=lambda kv: (-kv[1], kv[0]))[:10]
    assert [(e.table_id, e.joinability) for e in topk] == exp_sorted
    assert stats.verified_fp == 0 or stats.precision > 0.5


def test_no_false_negatives_end_to_end(lake):
    """Every injected joinable table must appear with full joinability."""
    corpus, index, query, q_cols, expected = lake
    k = len(expected) + 5
    topk, _ = discovery.discover(index, query, q_cols, k=k)
    got = {e.table_id: e.joinability for e in topk}
    for tid, j in expected.items():
        assert got.get(tid, -1) >= j, (tid, j, got.get(tid))


def test_sci_same_results_more_fps(lake):
    corpus, index, query, q_cols, _ = lake
    mate, s_mate = discovery.discover(index, query, q_cols, k=10, row_filter=True)
    sci, s_sci = discovery.discover(index, query, q_cols, k=10, row_filter=False)
    assert [(e.table_id, e.joinability) for e in mate] == [
        (e.table_id, e.joinability) for e in sci
    ]
    assert s_sci.verified_fp >= s_mate.verified_fp


def test_batched_engine_bit_identical(lake):
    """Acceptance bar: batched kernel-backed top-k == scalar path exactly —
    same table ids, same joinability scores, same mappings."""
    corpus, index, query, q_cols, _ = lake
    seq, _ = discovery.discover(index, query, q_cols, k=10)
    for backend in ("numpy", None):
        bat, _ = discover_many(index, [(query, q_cols)], k=10, backend=backend)[0]
        assert [(e.table_id, e.joinability, e.mapping) for e in seq] == [
            (e.table_id, e.joinability, e.mapping) for e in bat
        ]


def test_discover_many_bit_identical(lake):
    """One shared filter launch across queries == per-query discovery."""
    corpus, index, query, q_cols, _ = lake
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 3, 12, 2, seed=21
    )
    out = discover_many(index, queries, k=[10, 3, 5, 10])
    for (q, qc), k_i, (entries, stats) in zip(queries, [10, 3, 5, 10], out):
        seq, _ = discovery.discover(index, q, qc, k=k_i)
        assert [(e.table_id, e.joinability, e.mapping) for e in seq] == [
            (e.table_id, e.joinability, e.mapping) for e in entries
        ]
        assert stats.tables_fetched > 0


def test_discovery_engine_slot_batching(lake):
    from repro.serve.engine import DiscoveryEngine

    corpus, index, query, q_cols, _ = lake
    engine = DiscoveryEngine(index, batch=2)
    reqs = [engine.submit(query, q_cols, k=5) for _ in range(5)]
    assert not any(r.done for r in reqs)
    served = engine.flush()
    assert served == reqs and not engine.queue
    # the engine serves at the session default (quality rank), which only
    # reorders the scalar engine's verified set
    seq, _ = discovery.discover(index, query, q_cols, k=5)
    want = sorted((e.table_id, e.joinability) for e in seq)
    for r in served:
        assert r.done and r.stats is not None
        assert sorted((e.table_id, e.joinability) for e in r.results) == want
    one = engine.discover(query, q_cols, k=5)
    assert sorted((e.table_id, e.joinability) for e in one.results) == want


def test_512bit_engines_bit_identical(lake512):
    """512-bit end-to-end: discover_many on one request and on a group, and
    DiscoveryEngine.flush, all match the scalar Algorithm 1 scan exactly,
    mirroring the 128-bit assertions above (ids, scores, mappings)."""
    from repro.serve.engine import DiscoveryEngine

    corpus, index, query, q_cols, _ = lake512
    assert index.bits == 512 and index.cfg.lanes == 16
    assert index.superkeys.shape[1] == 16
    seq, _ = discovery.discover(index, query, q_cols, k=10)
    want = [(e.table_id, e.joinability, e.mapping) for e in seq]
    for backend in ("numpy", None):
        bat, _ = discover_many(index, [(query, q_cols)], k=10, backend=backend)[0]
        assert [(e.table_id, e.joinability, e.mapping) for e in bat] == want
    out = discover_many(index, [(query, q_cols)] * 3, k=10)
    for entries, _stats in out:
        assert [(e.table_id, e.joinability, e.mapping) for e in entries] == want
    engine = DiscoveryEngine(index, batch=2)
    assert engine.bits == 512
    # the engine defaults to rank='quality' + the profile gate: exact match
    # against the raw engine run at the SAME flags (and set-identical to the
    # count-ranked references above by the pure-pruning/reorder contract)
    want_q = [
        (e.table_id, e.joinability, e.mapping)
        for e in discover_many(
            index, [(query, q_cols)], k=10, rank="quality", profile_gate=True
        )[0][0]
    ]
    assert sorted(want_q) == sorted(want)
    reqs = [engine.submit(query, q_cols, k=10) for _ in range(3)]
    engine.flush()
    for r in reqs:
        assert [(e.table_id, e.joinability, e.mapping) for e in r.results] == want_q


def test_512bit_topk_matches_bruteforce(lake512):
    """No width ever changes the result set — only the FP rate (§6.3)."""
    corpus, index, query, q_cols, _ = lake512
    topk, _ = discovery.discover(index, query, q_cols, k=10)
    bf = discovery.topk_bruteforce(corpus, query, q_cols, 10)
    assert [(e.table_id, e.joinability) for e in topk] == bf


def test_batched_readback_accounting(lake):
    """Launch accounting of one request: a non-fused launch materialises its
    match matrix and reads the whole of it back, once.  Under the fused
    dispatch (MATE_FILTER_BACKEND=fused / TPU) the matrix is never produced
    at all — zero matrix bytes, and only the counts vector plus recomputed
    surviving slices are booked as read back."""
    from repro.kernels import ops

    corpus, index, query, q_cols, _ = lake
    _, st = discover_many(index, [(query, q_cols)], k=5)[0]
    if ops.fused_filter_default():
        assert st.filter_matrix_bytes == 0
        assert st.filter_fused_launches > 0
        # counts vectors + recomputed surviving slices, bounded by the
        # would-be matrix (every item × every key) + 4 count bytes/table
        assert st.filter_readback_bytes <= (
            st.pl_items_checked * len(
                dict.fromkeys(
                    tuple(row[c] for c in q_cols) for row in query.cells
                )
            ) + 4 * st.tables_fetched
        )
    else:
        assert st.filter_matrix_bytes > 0
        # the whole matrix comes back in one transfer, nothing else
        assert st.filter_readback_bytes == st.filter_matrix_bytes


@pytest.mark.parametrize("lazy", [False, True])
def test_score_tables_prunes_empty_tables_before_heap_fills(lake, lazy):
    """Rule 2 before the heap fills: the bound is 0, so every table whose
    exact filtered count is 0 is pruned without a slice readback or
    re-gather — counted in ``tables_pruned_empty`` and rule 2 alike — and
    the top-k equals verifying every table."""
    from repro.core import batched as B
    from repro.kernels import ops

    corpus, index, query, q_cols, _ = lake
    plan = B.plan_query(index, query, q_cols)
    block = plan.block
    n_tables = block.n_tables
    k = len(plan.distinct_keys)
    row_sk = index.superkey_of_rows(block.rows)
    hits = ops.subsume_np(row_sk, plan.q_sk) & plan.elig.dense()
    # empty every other table, so zero counts are certain
    seg = B._segment_ids(block.table_ptr)
    hits[seg % 2 == 1] = False
    plan.elig = ops.Eligibility(
        np.where(seg % 2 == 1, ops.PAD_ITEM_VALUE, plan.elig.item_value),
        plan.elig.key_value,
    )
    counts = np.bincount(seg, weights=hits.sum(axis=1), minlength=n_tables)
    counts = counts.astype(np.int32)
    empty = counts == 0
    assert empty.any() and not empty.all()

    topk = B._TopK(n_tables + 1)  # never fills: the bound stays 0
    st = plan.stats
    B._score_tables(
        index, plan, topk, None if lazy else hits, counts,
        row_sk=row_sk if lazy else None,
    )
    assert not topk.full
    assert st.tables_pruned_empty == st.tables_pruned_rule2 == int(empty.sum())
    assert st.tables_evaluated == n_tables
    if lazy:  # only surviving tables' slices were recomputed
        items = np.diff(block.table_ptr)
        assert st.filter_readback_bytes == int(items[~empty].sum()) * k

    ref = B._TopK(n_tables + 1)
    for t in range(n_tables):
        lo, hi = int(block.table_ptr[t]), int(block.table_ptr[t + 1])
        j, mapping = B._calculate_j(
            index, dataclasses.replace(plan, stats=discovery.DiscoveryStats()),
            block.rows[lo:hi], hits[lo:hi],
        )
        ref.offer(int(block.table_ids[t]), j, mapping)
    assert [(e.table_id, e.joinability, e.mapping) for e in topk.entries()] == [
        (e.table_id, e.joinability, e.mapping) for e in ref.entries()
    ]


@pytest.mark.parametrize("key_width", [3, 4])
def test_fp_heavy_queries_prune_empty_tables_bit_identical(lake, key_width):
    """Key columns from different tables (the fp-heavy mix): whole keys
    rarely match, heaps never fill, and most candidate tables have no
    filter-surviving pair.  The engine prunes those tables before any
    re-gather, alone or in a group, and still returns Algorithm 1's top-k."""
    corpus, index, _, _, _ = lake
    queries = synthetic.make_mixed_queries(corpus, 4, 40, key_width, seed=33)
    assert queries
    many = discover_many(index, queries, k=10)
    empty_many = empty_solo = 0
    for (q, qc), (entries, st_many) in zip(queries, many):
        seq, _ = discovery.discover(index, q, qc, k=10)
        want = [(e.table_id, e.joinability, e.mapping) for e in seq]
        assert [(e.table_id, e.joinability, e.mapping) for e in entries] == want
        solo, st_solo = discover_many(index, [(q, qc)], k=10)[0]
        assert [(e.table_id, e.joinability, e.mapping) for e in solo] == want
        empty_many += st_many.tables_pruned_empty
        empty_solo += st_solo.tables_pruned_empty
    assert empty_many > 0 and empty_solo > 0


@pytest.mark.parametrize("hash_name", ["bf", "ht", "murmur", "simhash"])
def test_baseline_hashes_same_topk(lake, hash_name):
    """Any hash gives the same RESULTS (no FNs) — only FP counts differ."""
    corpus, _, query, q_cols, _ = lake
    index = MateIndex(corpus, hash_name=hash_name)
    topk, _ = discovery.discover(index, query, q_cols, k=10)
    bf = discovery.topk_bruteforce(corpus, query, q_cols, 10)
    assert [(e.table_id, e.joinability) for e in topk] == bf


def test_mapping_argmax_permuted_columns():
    """Eq. 2: joinability maximises over column permutations."""
    corpus = Corpus(
        [
            Table(0, [["x", "b1", "a1"], ["y", "b2", "a2"], ["z", "b9", "a3"]]),
            Table(1, [["a1", "b1", "pad"], ["a9", "b9", "pad"]]),
        ]
    )
    query = Table(-1, [["a1", "b1"], ["a2", "b2"], ["a3", "b3"]])
    index = MateIndex(corpus)
    topk, _ = discovery.discover(index, query, [0, 1], k=2)
    by_id = {e.table_id: e for e in topk}
    # table 0 matches (a_i, b_i) under mapping (col2, col1) for rows 1-2
    assert by_id[0].joinability == 2
    assert by_id[0].mapping == (2, 1)
    assert by_id[1].joinability == 1


def test_key_width_3():
    corpus = Corpus(
        [
            Table(0, [["a", "b", "c", "zz"], ["a", "b", "d", "zz"]]),
            Table(1, [["c", "a", "b", "q"], ["x", "y", "z", "q"]]),
        ]
    )
    query = Table(-1, [["a", "b", "c"], ["a", "b", "d"]])
    index = MateIndex(corpus)
    topk, _ = discovery.discover(index, query, [0, 1, 2], k=2)
    by_id = {e.table_id: e.joinability for e in topk}
    assert by_id[0] == 2
    assert by_id[1] == 1


def test_init_column_modes(lake):
    corpus, index, query, q_cols, _ = lake
    for mode in ("cardinality", "order", "tls", "best", "worst"):
        col = discovery.init_column_selection(query, q_cols, mode, index)
        assert col in q_cols
    # best fetches no more PL items than worst
    def total(col):
        return sum(len(index.fetch_postings(v)) for v in set(query.column(col)))
    best = discovery.init_column_selection(query, q_cols, "best", index)
    worst = discovery.init_column_selection(query, q_cols, "worst", index)
    assert total(best) <= total(worst)


def test_table_filter_prunes(lake):
    corpus, index, query, q_cols, _ = lake
    _, stats = discovery.discover(index, query, q_cols, k=2)
    assert stats.tables_pruned_rule1 + stats.tables_pruned_rule2 > 0
    assert stats.tables_evaluated < stats.tables_fetched or stats.tables_fetched <= 2
