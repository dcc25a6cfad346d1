"""Gather-fused filter kernel: one launch from posting lists to counts.

The gather-fused path (``backend='fused-gather'``) must be BIT-IDENTICAL to
the host-gather composed path (``MateIndex.superkey_of_rows`` →
``ops.filter_table_counts``) at every hash width — per-table counts AND the
downstream top-k — while never gathering candidate superkeys on the host.
This suite pins that equivalence over the CSR edge shapes the serving tier
produces (empty posting lists, one-table blocks, all-tables-deleted,
zero-query plans) and across §5.4 mutations, where the device-resident
superkey store must refresh on every mutation-epoch bump.
"""

import numpy as np
import pytest

import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # property tests skip; unit tests still run
    HAVE_HYPOTHESIS = False

    def _skip_decorator(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis not installed")

    given = settings = _skip_decorator

    class _StrategyStub:
        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _StrategyStub()

from repro.core import discovery, xash
from repro.core.batched import discover_many, plan_and_count, score_from_counts
from repro.core.index import MateIndex
from repro.core.session import DiscoveryConfig, MateSession
from repro.data import synthetic
from repro.kernels import ops

RNG = np.random.default_rng(17)
ALL_BITS = (128, 256, 512)


def _oracle_counts(row_sk, q_sk, elig, seg, n_tables):
    hits = ops.subsume_np(row_sk, q_sk)
    if elig is not None:
        hits = hits & elig.dense()
    return np.bincount(
        np.asarray(seg)[np.asarray(seg) >= 0],
        weights=hits.sum(axis=1)[np.asarray(seg) >= 0],
        minlength=n_tables,
    ).astype(np.int32)


def _rand_case(lanes, n, q, n_tables, n_store=4096, seed=0):
    rng = np.random.default_rng(seed)
    store = rng.integers(0, 2**32, size=(n_store, lanes), dtype=np.uint32)
    rows = rng.integers(0, n_store, size=n).astype(np.int64)
    q_sk = rng.integers(0, 2**32, size=(q, lanes), dtype=np.uint32)
    # plant subsuming pairs so counts aren't trivially zero
    for k in range(0, q, 3):
        q_sk[k] = store[rows[k % max(n, 1)]] & rng.integers(
            0, 2**32, size=lanes, dtype=np.uint32
        )
    # init-value ids: each key is eligible with the items of its value
    elig = ops.Eligibility(
        rng.integers(0, 2, size=n).astype(np.int32),
        rng.integers(0, 2, size=q).astype(np.int32),
    )
    seg = np.sort(rng.integers(0, n_tables, size=n)).astype(np.int32)
    return store, rows, q_sk, elig, seg


# ---------------------------------------------------------------------------
# Kernel/ops-level bit-identity vs the host-gather composed launch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", ALL_BITS)
@pytest.mark.parametrize("n,q,n_tables", [
    (700, 23, 19),    # non-pow2 everything
    (1030, 70, 13),   # row count crossing the 1024 block boundary
    (257, 5, 1),      # single-table CSR block
    (64, 3, 5),       # tiny block below every bucket minimum
])
def test_gather_counts_match_host_gather(bits, n, q, n_tables):
    lanes = xash.XashConfig(bits=bits).lanes
    store, rows, q_sk, elig, seg = _rand_case(lanes, n, q, n_tables, seed=bits + n)
    composed = ops.filter_table_counts(store[rows], q_sk, elig, seg, n_tables)
    gathered = ops.gather_filter_table_counts(
        ops.device_store(store), rows, q_sk, elig, seg, n_tables
    )
    assert np.array_equal(gathered, composed), (bits, n, q, n_tables)
    assert np.array_equal(
        gathered, _oracle_counts(store[rows], q_sk, elig, seg, n_tables)
    )


@pytest.mark.parametrize("bits", ALL_BITS)
def test_gather_dispatch_counts_only_no_host_superkeys(bits):
    """The fused-gather dispatch accepts row_sk=None — the host never gathers
    — and returns composed-identical counts with the filter's own
    survivors, item-major (item, key) pairs, in place of a hits matrix."""
    lanes = xash.XashConfig(bits=bits).lanes
    store, rows, q_sk, elig, seg = _rand_case(lanes, 420, 17, 7, seed=bits)
    pairs, counts = ops.filter_hits_table_counts(
        None, q_sk, elig, seg, 7, backend="fused-gather",
        store=ops.device_store(store), rows=rows,
    )
    assert isinstance(pairs, ops.Pairs)
    want = ops.filter_table_counts(store[rows], q_sk, elig, seg, 7)
    assert np.array_equal(counts, want)
    rs, ks = np.nonzero(ops.subsume_np(store[rows], q_sk) & elig.dense())
    assert np.array_equal(pairs.rows, rs) and np.array_equal(pairs.keys, ks)
    assert pairs.rows.size == int(counts.sum()) > 0


def _packed_reference(store, rows, q_sk, elig, seg, n_queries, qb):
    """The kernel's hit words from the dense masked reference: bit
    ``k % 32`` of word ``[k // 32, i]`` where row ``i`` passes key ``k``
    (``subsume_np`` ∧ ``Eligibility.dense()``, padding rows and padded key
    columns cleared)."""
    hits = np.zeros((rows.shape[0], qb), dtype=bool)
    lanes = q_sk.shape[1]
    hits[:, :n_queries] = ops.subsume_np(store[rows][:, :lanes], q_sk[:n_queries])
    if elig is not None:
        hits[:, :n_queries] &= elig.dense()
    hits[seg < 0] = False
    bits = hits.reshape(rows.shape[0], qb // 32, 32).astype(np.uint64)
    words = (bits << np.arange(32, dtype=np.uint64)).sum(axis=2)
    return words.astype(np.uint32).T  # [qb / 32, n]


@pytest.mark.parametrize("lanes,store_lanes", [(4, 4), (8, 8), (16, 16), (4, 16)])
@pytest.mark.parametrize("use_elig", [True, False])
@pytest.mark.parametrize("n_q,qb", [(45, 64), (300, 512)])
def test_gather_kernel_packed_words_equal_dense_reference(
    lanes, store_lanes, use_elig, n_q, qb
):
    """The kernel's packed hit tile, bit for bit, against the dense masked
    reference: padded rows (seg -1) and padded query columns (all-ones
    keys) set no bit, an all-ones row super key passes every real key its
    eligibility allows, and a lane-prefix probe (4 of 16 lanes) packs the
    prefix test's hits, over one query block and over two.  Its set bits
    sum to the per-table counts."""
    import jax.numpy as jnp

    from repro.kernels import filter_kernel

    n, n_tables = 1024, 9
    store, rows, q_sk, elig, seg = _rand_case(
        store_lanes, n, n_q, n_tables, n_store=512, seed=lanes * store_lanes
    )
    store[7] = 0xFFFFFFFF
    rows[::37] = 7  # saturated rows subsume every key, the padding keys too
    seg[-100:] = -1  # padding rows
    q_sk = q_sk[:, :lanes]
    qry = np.full((qb, lanes), 0xFFFFFFFF, dtype=np.uint32)
    qry[:n_q] = q_sk
    elig_ids = None if not use_elig else tuple(map(jnp.asarray, elig.padded(n, qb)))
    counts, words = filter_kernel.gather_filter_table_counts(
        jnp.asarray(rows.astype(np.int32)),
        ops.device_store(store).lines,
        jnp.asarray(qry).T,
        elig_ids,
        jnp.asarray(seg),
        store_lanes=store_lanes, n_tables=128, n_queries=n_q,
        block_n=256, block_q=min(qb, filter_kernel.DEFAULT_BLOCK_Q),
        interpret=True,
    )
    want = _packed_reference(
        store, rows, qry, elig if use_elig else None, seg, n_q, qb
    )
    got = np.asarray(words).view(np.uint32)
    assert np.array_equal(got, want)
    # the padded key columns stay clear, the saturated rows' too
    assert not (got[n_q // 32] >> np.uint32(n_q % 32)).any()
    assert not got[n_q // 32 + 1 :].any()
    assert got[:, rows == 7].any()
    n_bits = int(np.unpackbits(got.view(np.uint8)).sum())
    assert n_bits == int(np.asarray(counts).sum()) > 0


@pytest.mark.parametrize("n_words,n_items,n_live", [
    (2, 512, 0),  # nothing survived
    (2, 512, 1),
    (3, 1000, 1500),  # item count off the 128 block
    (4, 1 << 15, 20000),  # three levels of running count
    (8, 64, 512),  # every word nonzero
])
def test_nonzero_words_item_major(n_words, n_items, n_live):
    """The device compaction returns the nonzero words item-major, exactly
    ``np.flatnonzero`` of the transposed words, with the free slots
    past them holding index ``n_words · n_items`` and word 0."""
    rng = np.random.default_rng(n_live)
    words = np.zeros((n_words, n_items), np.int32)
    at = rng.choice(words.size, size=n_live, replace=False)
    words.reshape(-1)[at] = rng.integers(1, 1 << 31, size=n_live) * rng.choice([-1, 1], n_live)
    flat = words.T.reshape(-1)
    want = np.flatnonzero(flat)
    size = ops._pow2_bucket(max(n_live, 1), ops._MIN_PAIRS_BUCKET)
    got_at, got_word = map(np.asarray, ops._nonzero_words(jnp.asarray(words), size=size))
    assert np.array_equal(got_at[:n_live], want)
    assert np.array_equal(got_word[:n_live], flat[want])
    assert (got_at[n_live:] == words.size).all() and not got_word[n_live:].any()


def test_gather_lane_prefix_degrade_over_full_width_store():
    """The serving tier's degrade path probes a lane PREFIX of the query
    keys against the full-width device store — counts must equal the
    composed launch over prefix-sliced host-gathered superkeys."""
    store, rows, q_sk16, elig, seg = _rand_case(16, 900, 31, 11, seed=3)
    for probe_lanes in (4, 8, 16):
        q_sk = q_sk16[:, :probe_lanes]
        composed = ops.filter_table_counts(
            store[rows][:, :probe_lanes], q_sk, elig, seg, 11
        )
        gathered = ops.gather_filter_table_counts(
            ops.device_store(store), rows, q_sk, elig, seg, 11
        )
        assert np.array_equal(gathered, composed), probe_lanes


def test_gather_zero_shapes_short_circuit():
    store = ops.device_store(RNG.integers(0, 2**32, size=(64, 4), dtype=np.uint32))
    zq = np.zeros((0, 4), dtype=np.uint32)
    assert ops.gather_filter_table_counts(
        store, np.zeros(0, np.int64), zq, None, np.zeros(0, np.int32), 5
    ).tolist() == [0] * 5
    assert ops.gather_filter_table_counts(
        store, np.arange(10), zq, None, np.zeros(10, np.int32), 5
    ).tolist() == [0] * 5
    assert ops.gather_filter_table_counts(
        store, np.arange(10), RNG.integers(0, 2**32, size=(3, 4), dtype=np.uint32),
        None, np.zeros(10, np.int32), 0,
    ).shape == (0,)


def test_gather_table_cap_raises_on_direct_call():
    store = ops.device_store(RNG.integers(0, 2**32, size=(64, 4), dtype=np.uint32))
    big = ops._FUSED_MAX_TABLES + 1
    with pytest.raises(ValueError, match="at most"):
        ops.gather_filter_table_counts(
            store, np.arange(10), RNG.integers(0, 2**32, size=(3, 4), dtype=np.uint32),
            None, np.zeros(10, np.int32), big,
        )


# ---------------------------------------------------------------------------
# Engine-level: CSR edge shapes, bit-identical top-k, accounting
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lake():
    spec = synthetic.SyntheticSpec(n_tables=150, seed=0)
    corpus = synthetic.make_corpus(spec)
    query, q_cols, _expected, corpus = synthetic.make_query_with_ground_truth(corpus)
    return corpus, query, q_cols


@pytest.mark.parametrize("bits", ALL_BITS)
def test_gather_engine_topk_bit_identical(lake, bits):
    """discover_many(backend='fused-gather') == scalar Algorithm 1 at
    every width and at heap sizes that fill early and late, with zero matrix
    bytes and positive gather savings."""
    corpus, query, q_cols = lake
    index = MateIndex(corpus, cfg=xash.XashConfig(bits=bits))
    for k in (3, 10):
        seq, _ = discovery.discover(index, query, q_cols, k=k)
        bat, st = discover_many(
            index, [(query, q_cols)], k=k, backend="fused-gather"
        )[0]
        assert [(e.table_id, e.joinability, e.mapping) for e in bat] == [
            (e.table_id, e.joinability, e.mapping) for e in seq
        ]
        assert st.filter_matrix_bytes == 0
        assert st.filter_fused_launches > 0
        # every launch saved n × (lanes·4 − 4) bytes of host gather traffic
        assert st.gather_bytes_saved > 0


def test_gather_discover_many_and_two_phase(lake):
    """Group launch (plan_and_count → score_from_counts) on the gather path:
    bit-identical to per-query discovery; PlanCounts carries no host
    superkeys (row_sk None) and replays from the index store."""
    corpus, query, q_cols = lake
    index = MateIndex(corpus)
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 12, 2, seed=21
    )
    out = discover_many(index, queries, k=[10, 3, 5], backend="fused-gather")
    for (q, qc), k_i, (entries, st) in zip(queries, [10, 3, 5], out):
        seq, _ = discovery.discover(index, q, qc, k=k_i)
        assert [(e.table_id, e.joinability, e.mapping) for e in seq] == [
            (e.table_id, e.joinability, e.mapping) for e in entries
        ]
        assert st.filter_matrix_bytes == 0
        assert st.filter_fused_launches == 1
        assert st.gather_bytes_saved > 0
    pcs = plan_and_count(index, queries, "fused-gather")
    for pc, ((q, qc), (want, _)) in zip(pcs, zip(queries, out)):
        assert pc.row_sk is None and pc.fused
        assert pc.gather_saved == pc.plan.block.n_items * (index.cfg.lanes * 4 - 4)
        got, st = score_from_counts(index, pc, k=10)
        ref, _ = discovery.discover(index, q, qc, k=10)
        assert [(e.table_id, e.joinability) for e in got] == [
            (e.table_id, e.joinability) for e in ref
        ]
        # cached replay: scoring again from the cacheable copy stays identical
        got2, st2 = score_from_counts(index, pc.cacheable(), k=10, from_cache=True)
        assert [(e.table_id, e.joinability) for e in got2] == [
            (e.table_id, e.joinability) for e in got
        ]
        assert st2.gather_bytes_saved == 0  # an earlier request paid the launch


def test_gather_empty_posting_lists(lake):
    """A query whose init-column values miss the index entirely: empty CSR
    block, zero launches, empty top-k — identical to the scalar engine."""
    corpus, _query, _q_cols = lake
    index = MateIndex(corpus)
    ghost = synthetic.Table(-1, [["zzznope", "zzznope2"]] * 3)
    seq, _ = discovery.discover(index, ghost, [0, 1], k=5)
    bat, st = discover_many(
        index, [(ghost, [0, 1])], k=5, backend="fused-gather"
    )[0]
    assert [(e.table_id, e.joinability) for e in bat] == [
        (e.table_id, e.joinability) for e in seq
    ]
    assert st.gather_bytes_saved == 0  # nothing to gather, nothing saved


def test_gather_all_candidates_one_table():
    """CSR block with a single candidate table (one-table corpus)."""
    cells = [[f"k{r}", f"v{r % 3}", "common"] for r in range(9)]
    corpus = synthetic.Corpus([synthetic.Table(0, cells)])
    index = MateIndex(corpus)
    query = synthetic.Table(-1, [[f"k{r}", f"v{r % 3}"] for r in range(5)])
    seq, _ = discovery.discover(index, query, [0, 1], k=3)
    bat, st = discover_many(
        index, [(query, [0, 1])], k=3, backend="fused-gather"
    )[0]
    assert [(e.table_id, e.joinability, e.mapping) for e in bat] == [
        (e.table_id, e.joinability, e.mapping) for e in seq
    ]
    assert st.filter_fused_launches == 1


def test_gather_all_tables_deleted(lake):
    """Every candidate table tombstoned: fetch_postings filters everything,
    the CSR block is empty, and the gather path returns an empty top-k."""
    corpus, query, q_cols = lake
    index = MateIndex(corpus)
    ref, _ = discover_many(
        index, [(query, q_cols)], k=5, backend="fused-gather"
    )[0]
    assert ref  # sanity: undeleted lake finds joinable tables
    for t in range(len(corpus.tables)):
        index.delete_table(t)
    got, st = discover_many(
        index, [(query, q_cols)], k=5, backend="fused-gather"
    )[0]
    assert got == []
    assert st.gather_bytes_saved == 0
    seq, _ = discovery.discover(index, query, q_cols, k=5)
    assert seq == []


def test_gather_zero_query_plan_is_safe():
    """plan_and_count([]) and a zero-row query table short-circuit."""
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=20, seed=4))
    index = MateIndex(corpus)
    assert plan_and_count(index, [], "fused-gather") == []


# ---------------------------------------------------------------------------
# §5.4 mutations: the device store must refresh on every epoch bump
# ---------------------------------------------------------------------------

def _store_rows(store):
    """Unpack a packed ``ops.DeviceStore`` back to uint32[n_rows, lanes]."""
    flat = np.asarray(store.lines).reshape(-1)
    return flat[: store.n_rows * store.lanes].reshape(store.n_rows, store.lanes)


def test_device_store_refreshes_on_epoch_bump():
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=30, seed=8))
    index = MateIndex(corpus)
    s0 = index.device_store()
    assert s0 is index.device_store()  # cached within an epoch
    assert np.array_equal(_store_rows(s0), index.superkeys)
    index.delete_table(0)  # in-place zeroing + epoch bump
    s1 = index.device_store()
    assert s1 is not s0
    assert np.array_equal(_store_rows(s1), index.superkeys)
    assert _store_rows(s1)[: int(corpus.row_base[1])].sum() == 0
    index.update_cell(1, 0, 0, "mutated-value")  # in-place row rewrite
    s2 = index.device_store()
    assert s2 is not s1
    assert np.array_equal(_store_rows(s2), index.superkeys)
    tid = index.insert_table([["a", "b"], ["c", "d"]])
    s3 = index.device_store()
    assert s3.n_rows == index.superkeys.shape[0] > s2.n_rows
    assert np.array_equal(_store_rows(s3), index.superkeys)
    assert tid == len(index.corpus.tables) - 1
    # packed layout: whole (8, 128) tiles, exactly the logical bytes + tail
    assert s3.lines.shape[1] == 128 and s3.lines.shape[0] % 8 == 0
    assert s3.lines.nbytes - index.superkeys.nbytes < 8 * 128 * 4


def test_gather_bit_identical_across_mutations(lake):
    """Insert/update/delete between launches: the gather path must keep
    matching the scalar engine after every §5.4 mutation (stale device
    stores would poison the filter silently)."""
    corpus, query, q_cols = lake
    index = MateIndex(corpus)

    def check():
        seq, _ = discovery.discover(index, query, q_cols, k=8)
        bat, st = discover_many(
            index, [(query, q_cols)], k=8, backend="fused-gather"
        )[0]
        assert [(e.table_id, e.joinability, e.mapping) for e in bat] == [
            (e.table_id, e.joinability, e.mapping) for e in seq
        ]
        return seq

    check()
    key_cells = [[query.cells[r][c] for c in q_cols] for r in range(query.n_rows)]
    tid = index.insert_table([kc + ["extra"] for kc in key_cells])
    seq = check()
    assert tid in [e.table_id for e in seq]  # the new table is discoverable
    index.update_cell(tid, 0, len(key_cells[0]), "mutated")
    check()
    index.delete_table(int(seq[0].table_id))
    check()


def test_gather_store_budget_demotes_to_host_gather(lake, monkeypatch):
    """A store over the device budget demotes fused-gather to the host-gather
    fused launch: identical results, zero gather savings claimed."""
    corpus, query, q_cols = lake
    index = MateIndex(corpus)
    want, _ = discovery.discover(index, query, q_cols, k=10)
    monkeypatch.setattr(ops, "GATHER_STORE_MAX_BYTES", 0)
    got, st = discover_many(
        index, [(query, q_cols)], k=10, backend="fused-gather"
    )[0]
    assert [(e.table_id, e.joinability, e.mapping) for e in got] == [
        (e.table_id, e.joinability, e.mapping) for e in want
    ]
    assert st.gather_bytes_saved == 0
    assert st.filter_fused_launches > 0  # demoted to fused, not to composed
    assert st.gather_demotions > 0


def test_gather_table_cap_demotes_per_batch(lake, monkeypatch):
    """A launch above the scatter-tile table cap splits into table chunks
    (one gather-fused kernel call each) and stays on the gather path —
    results bit-identical, counts-only."""
    corpus, query, q_cols = lake
    index = MateIndex(corpus)
    seq, _ = discovery.discover(index, query, q_cols, k=10)
    monkeypatch.setattr(ops, "_FUSED_MAX_TABLES", 4)
    calls = []
    launch = ops.gather_filter_survivors

    def spy(store, rows, *args, **kw):
        calls.append(rows.shape[0])
        return launch(store, rows, *args, **kw)

    monkeypatch.setattr(ops, "gather_filter_survivors", spy)
    bat, st = discover_many(
        index, [(query, q_cols)], k=10, backend="fused-gather"
    )[0]
    assert [(e.table_id, e.joinability, e.mapping) for e in bat] == [
        (e.table_id, e.joinability, e.mapping) for e in seq
    ]
    # every chunk but the last holds exactly the cap's tables
    n_tables = st.tables_fetched - st.tables_gated
    assert len(calls) == -(-n_tables // 4) > 1
    assert sum(calls) == st.pl_items_checked
    assert st.gather_bytes_saved > 0
    assert st.filter_fused_launches > 0
    assert st.filter_matrix_bytes == 0
    assert st.gather_demotions == 0
    # each chunk's survivors, re-based to the launch's items, concatenate
    assert st.filter_pairs_returned == st.filter_passed > 0
    assert st.regather_pairs == 0


def test_gather_session_and_serving_inherit(lake):
    """MateSession and the serving tier's plan_and_count seam run the gather
    path unchanged (the BoundCache stores row_sk-free PlanCounts)."""
    corpus, query, q_cols = lake
    session = MateSession(
        MateIndex(corpus, cfg=xash.XashConfig(bits=256)),
        DiscoveryConfig(backend="fused-gather", k=10),
    )
    ref, _ = discovery.discover(session.index, query, q_cols, k=10)
    got, stats = session.discover(query, q_cols)
    # session default rank='quality' (ISSUE 9) reorders without changing
    # membership — the gather contract here is the SET + the byte counters
    assert sorted((e.table_id, e.joinability) for e in got) == sorted(
        (e.table_id, e.joinability) for e in ref
    )
    assert stats.gather_bytes_saved > 0
    assert session.stats.gather_bytes_saved == stats.gather_bytes_saved
    pcs = session.plan_and_count([(query, q_cols)], filter_lanes=4)
    assert pcs[0].row_sk is None
    entries, st = session.score_from_counts(pcs[0], k=10)
    assert sorted((e.table_id, e.joinability) for e in entries) == sorted(
        (e.table_id, e.joinability) for e in ref
    )
    assert st.filter_lanes == 4  # degraded launch, set-identical results


# ---------------------------------------------------------------------------
# Property suite (hypothesis-optional, like tests/test_xash.py)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    bits=st.sampled_from(ALL_BITS),
    n=st.integers(min_value=1, max_value=600),
    q=st.integers(min_value=1, max_value=40),
    n_tables=st.integers(min_value=1, max_value=50),
    seed=st.integers(min_value=0, max_value=2**16),
    use_elig=st.booleans(),
)
def test_gather_property_bit_identity(bits, n, q, n_tables, seed, use_elig):
    """For arbitrary CSR shapes, the gather-fused launch equals the
    host-gather composed launch bit-for-bit at 128/256/512 bits."""
    lanes = xash.XashConfig(bits=bits).lanes
    store, rows, q_sk, elig, seg = _rand_case(
        lanes, n, q, n_tables, n_store=1024, seed=seed
    )
    if not use_elig:
        elig = None
    composed = ops.filter_table_counts(store[rows], q_sk, elig, seg, n_tables)
    gathered = ops.gather_filter_table_counts(
        ops.device_store(store), rows, q_sk, elig, seg, n_tables
    )
    assert np.array_equal(gathered, composed)
