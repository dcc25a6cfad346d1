"""Fused filter+segment-count kernel: bit-identical counts vs the composed
oracles, CSR edge shapes, and engine top-k identity on the counts-only path.

The fused kernel (``filter_kernel.filter_table_counts``) must reproduce the
composed pipeline (subsumption matrix ∧ eligibility → row sum → segment sum)
EXACTLY at every hash width — counts are integral, so equality is exact.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import discovery, xash
from repro.core.batched import discover_many
from repro.core.session import DiscoveryConfig
from repro.core.index import MateIndex
from repro.data import synthetic
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _rand_sks(n, lanes, dense_frac=0.1):
    """Random superkeys with a dense (all-ones) head so some rows subsume."""
    sk = RNG.integers(0, 2**32, size=(n, lanes), dtype=np.uint32)
    sk[: max(1, int(n * dense_frac))] = 0xFFFFFFFF
    return sk


def _rand_elig(n, q, n_values=2):
    """Random init-value ids: each key is eligible with the items of its value."""
    return ops.Eligibility(
        RNG.integers(0, n_values, size=n).astype(np.int32),
        RNG.integers(0, n_values, size=q).astype(np.int32),
    )


def _all_elig(n, q):
    return ops.Eligibility(np.zeros(n, np.int32), np.zeros(q, np.int32))


def _oracle_counts(row_sk, q_sk, elig, seg, n_tables):
    hits = ops.subsume_np(row_sk, q_sk) & elig.dense()
    return np.bincount(
        seg, weights=hits.sum(axis=1), minlength=n_tables
    ).astype(np.int32)


@pytest.mark.parametrize("bits", [128, 256, 512])
@pytest.mark.parametrize("n,q,n_tables", [
    (700, 23, 19),    # non-pow2 everything
    (1030, 70, 13),   # row count crossing the 1024 block boundary
    (257, 5, 1),      # single-table CSR block
    (64, 3, 5),       # tiny block below every bucket minimum
])
def test_fused_counts_match_composed_oracles(bits, n, q, n_tables):
    """Fused kernel == numpy oracle == XLA `_per_table_counts` composition,
    bit-identically, at 4/8/16 lanes on non-pow2 CSR shapes."""
    lanes = xash.XashConfig(bits=bits).lanes
    row_sk = _rand_sks(n, lanes)
    q_sk = RNG.integers(0, 2**32, size=(q, lanes), dtype=np.uint32)
    q_sk[0] = 0  # zero (empty-key) query subsumes everything
    elig = _rand_elig(n, q)
    seg = np.sort(RNG.integers(0, n_tables, size=n)).astype(np.int32)
    want = _oracle_counts(row_sk, q_sk, elig, seg, n_tables)
    got = ops.filter_table_counts(row_sk, q_sk, elig, seg, n_tables)
    assert np.array_equal(got, want), (bits, n, q, n_tables)
    # composed XLA reduction the kernel replaces (jit'd _per_table_counts)
    hits = jnp.asarray(ops.subsume_np(row_sk, q_sk) & elig.dense())
    composed = np.asarray(
        ops._per_table_counts(hits, jnp.asarray(seg), n_tables)
    )
    assert np.array_equal(got, composed)


@pytest.mark.parametrize("bits", [128, 256, 512])
def test_fused_dispatch_returns_counts_only(bits):
    """`filter_hits_table_counts(backend='fused')` returns hits=None (the
    matrix was never produced) and oracle-identical counts at every width."""
    lanes = xash.XashConfig(bits=bits).lanes
    n, q, n_tables = 420, 17, 7
    row_sk = _rand_sks(n, lanes)
    q_sk = RNG.integers(0, 2**32, size=(q, lanes), dtype=np.uint32)
    elig = _rand_elig(n, q)
    seg = np.sort(RNG.integers(0, n_tables, size=n)).astype(np.int32)
    hits, counts = ops.filter_hits_table_counts(
        row_sk, q_sk, elig, seg, n_tables, backend="fused"
    )
    assert hits is None
    assert np.array_equal(counts, _oracle_counts(row_sk, q_sk, elig, seg, n_tables))


def test_fused_env_backend_dispatch(monkeypatch):
    """MATE_FILTER_BACKEND=fused routes the default dispatch to the fused
    kernel (the CI `pallas-interpret-fused` leg's contract)."""
    monkeypatch.setenv("MATE_FILTER_BACKEND", "fused")
    assert ops.fused_filter_default()
    n, q, n_tables = 300, 9, 4
    row_sk = _rand_sks(n, 4)
    q_sk = RNG.integers(0, 2**32, size=(q, 4), dtype=np.uint32)
    elig = _all_elig(n, q)
    seg = np.sort(RNG.integers(0, n_tables, size=n)).astype(np.int32)
    hits, counts = ops.filter_hits_table_counts(row_sk, q_sk, elig, seg, n_tables)
    assert hits is None
    assert np.array_equal(counts, _oracle_counts(row_sk, q_sk, elig, seg, n_tables))


def test_fused_zero_query_and_empty_blocks():
    """Zero queries / zero rows / zero tables short-circuit; an all-false
    eligibility (fully pruned batch) yields all-zero counts."""
    row_sk = _rand_sks(100, 4)
    q_sk = np.zeros((0, 4), dtype=np.uint32)
    assert np.array_equal(
        ops.filter_table_counts(row_sk, q_sk, _all_elig(100, 0),
                                np.zeros(100, np.int32), 5),
        np.zeros(5, np.int32),
    )
    assert ops.filter_table_counts(
        np.zeros((0, 4), np.uint32), _rand_sks(3, 4), _all_elig(0, 3),
        np.zeros(0, np.int32), 5,
    ).tolist() == [0] * 5
    assert ops.filter_table_counts(
        row_sk, _rand_sks(3, 4), _all_elig(100, 3),
        np.zeros(100, np.int32), 0,
    ).shape == (0,)
    # all-pruned: every (row, key) pair ineligible (no key has an item's value)
    counts = ops.filter_table_counts(
        row_sk, np.zeros((3, 4), np.uint32),
        ops.Eligibility(np.zeros(100, np.int32), np.ones(3, np.int32)),
        np.sort(RNG.integers(0, 5, 100)).astype(np.int32), 5,
    )
    assert np.array_equal(counts, np.zeros(5, np.int32))


def test_fused_counts_large_table_counts():
    """Regression: when the VMEM budget shrinks block_n (tb > 1024), the
    block size must still divide the padded row count — a non-divisor grid
    silently drops trailing rows.  Also pins the >cap split into table
    chunks (still counts-only, same counts)."""
    from repro.kernels import filter_kernel

    n, q, n_tables = 8192, 64, 1100  # tb=1152 → budget block_n < 1024
    row_sk = _rand_sks(n, 4)
    q_sk = RNG.integers(0, 2**32, size=(q, 4), dtype=np.uint32)
    elig = _rand_elig(n, q)
    seg = np.sort(RNG.integers(0, n_tables, size=n)).astype(np.int32)
    want = _oracle_counts(row_sk, q_sk, elig, seg, n_tables)
    got = ops.filter_table_counts(row_sk, q_sk, elig, seg, n_tables)
    assert np.array_equal(got, want)
    # block helper: always a power of two in [128, 1024], within budget
    for tb in (128, 1024, 1152, 4096, 8192):
        b = filter_kernel.fused_block_n(tb)
        assert b & (b - 1) == 0 and 128 <= b <= 1024
        assert b == 128 or b * tb <= filter_kernel.FUSED_ONEHOT_BUDGET
    # above the cap the dispatch splits into table chunks (hits None, same
    # counts)
    big = filter_kernel.FUSED_MAX_TABLES + 1
    seg_big = np.sort(RNG.integers(0, big, size=300)).astype(np.int32)
    seg_big[-1] = big - 1  # the last chunk holds one table
    elig_small = ops.Eligibility(elig.item_value[:300], elig.key_value[:5])
    hits, counts = ops.filter_hits_table_counts(
        row_sk[:300], q_sk[:5], elig_small, seg_big, big, backend="fused"
    )
    assert hits is None
    assert np.array_equal(
        counts, _oracle_counts(row_sk[:300], q_sk[:5], elig_small, seg_big, big)
    )


def test_fused_saturated_rows_ignore_padded_queries():
    """Regression: a saturated (all-ones) row super key subsumes the all-ones
    PADDED query columns too — without an eligibility mask (elig=None) those
    phantom columns must still contribute nothing, in both modes."""
    n, q, n_tables = 10, 5, 2  # q pads to 64: 59 phantom columns
    row_sk = RNG.integers(0, 2**32, size=(n, 4), dtype=np.uint32)
    row_sk[0] = 0xFFFFFFFF  # saturated row
    q_sk = RNG.integers(0, 2**32, size=(q, 4), dtype=np.uint32)
    seg = np.sort(RNG.integers(0, n_tables, size=n)).astype(np.int32)
    match = ops.subsume_np(row_sk, q_sk)
    want_sum = np.bincount(seg, weights=match.sum(1), minlength=n_tables)
    got_sum = ops.filter_table_counts(row_sk, q_sk, None, seg, n_tables)
    assert np.array_equal(got_sum, want_sum.astype(np.int32))
    want_any = np.bincount(seg, weights=match.any(1), minlength=n_tables)
    got_any = ops.filter_table_counts(
        row_sk, q_sk, None, seg, n_tables, mode="any"
    )
    assert np.array_equal(got_any, want_any.astype(np.int32))


def test_fused_false_pins_composed_path(lake, monkeypatch):
    """Regression: an explicit composed backend must stick even when the
    env/TPU default dispatch is fused — the composed path materialises the
    matrix (matrix_bytes > 0) and reports zero fused launches.  (The legacy
    fused=False spelling of this pin is covered in test_session.)"""
    corpus, index, query, q_cols = lake
    monkeypatch.setenv("MATE_FILTER_BACKEND", "fused")
    seq, _ = discovery.discover(index, query, q_cols, k=10)
    bat, st = discover_many(index, [(query, q_cols)], k=10, backend="pallas")[0]
    assert [(e.table_id, e.joinability) for e in bat] == [
        (e.table_id, e.joinability) for e in seq
    ]
    assert st.filter_fused_launches == 0
    assert st.filter_matrix_bytes > 0


def test_fused_table_cap_fallback_accounting(lake, monkeypatch):
    """Above the table cap a fused launch splits into table chunks: results
    stay bit-identical and the counts-only contract still holds."""
    corpus, index, query, q_cols = lake
    monkeypatch.setattr(ops, "_FUSED_MAX_TABLES", 4)  # force the split
    seq, _ = discovery.discover(index, query, q_cols, k=10)
    bat, st = discover_many(index, [(query, q_cols)], k=10, backend="fused")[0]
    assert [(e.table_id, e.joinability) for e in bat] == [
        (e.table_id, e.joinability) for e in seq
    ]
    assert st.filter_fused_launches > 0
    assert st.filter_matrix_bytes == 0


def test_fused_mode_any_matches_distributed_semantics():
    """mode='any' (rows with ≥1 hit per table) == the distributed filter's
    per-table reduction, including -1 padding rows and elig=None."""
    n, q, n_tables = 500, 11, 9
    row_sk = _rand_sks(n, 4)
    q_sk = RNG.integers(0, 2**32, size=(q, 4), dtype=np.uint32)
    seg = RNG.integers(0, n_tables, size=n).astype(np.int32)
    seg[-7:] = -1  # padding rows must scatter nowhere
    got = ops.filter_table_counts(row_sk, q_sk, None, seg, n_tables, mode="any")
    match = ops.subsume_np(row_sk, q_sk) & (seg >= 0)[:, None]
    want = np.bincount(
        seg[seg >= 0], weights=match.any(axis=1)[seg >= 0], minlength=n_tables
    ).astype(np.int32)
    assert np.array_equal(got, want)


@pytest.fixture(scope="module")
def lake():
    spec = synthetic.SyntheticSpec(n_tables=150, seed=0)
    corpus = synthetic.make_corpus(spec)
    query, q_cols, expected, corpus = synthetic.make_query_with_ground_truth(corpus)
    index = MateIndex(corpus)
    return corpus, index, query, q_cols


def test_fused_engine_topk_bit_identical(lake):
    """Engine acceptance: the fused counts-only path returns the same top-k
    (ids, scores, mappings) as scalar Algorithm 1, with ZERO match-matrix
    bytes, at heap sizes that fill early (rule 1 prunes) and late."""
    corpus, index, query, q_cols = lake
    for k in (1, 3, 10):
        seq, _ = discovery.discover(index, query, q_cols, k=k)
        want = [(e.table_id, e.joinability, e.mapping) for e in seq]
        bat, st = discover_many(index, [(query, q_cols)], k=k, backend="fused")[0]
        assert [(e.table_id, e.joinability, e.mapping) for e in bat] == want
        assert st.filter_matrix_bytes == 0
        assert st.filter_fused_launches > 0
        assert st.readback_frac == 0.0  # no matrix → frac defined as 0


def test_fused_discover_many_and_engine(lake):
    """Group (discover_many) and serving (DiscoveryEngine) fused paths are
    bit-identical to per-query discovery with counts-only group launches."""
    from repro.serve.engine import DiscoveryEngine

    corpus, index, query, q_cols = lake
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 12, 2, seed=21
    )
    out = discover_many(index, queries, k=[10, 3, 5], backend="fused")
    for (q, qc), k_i, (entries, st) in zip(queries, [10, 3, 5], out):
        seq, _ = discovery.discover(index, q, qc, k=k_i)
        assert [(e.table_id, e.joinability, e.mapping) for e in seq] == [
            (e.table_id, e.joinability, e.mapping) for e in entries
        ]
        assert st.filter_matrix_bytes == 0
        assert st.filter_fused_launches == 1
    engine = DiscoveryEngine(
        index, batch=2, config=DiscoveryConfig(backend="fused")
    )
    reqs = [engine.submit(q, qc, k=5) for q, qc in queries]
    engine.flush()
    for (q, qc), r in zip(queries, reqs):
        seq, _ = discovery.discover(index, q, qc, k=5)
        # the session defaults to rank='quality' (ISSUE 9) which reorders
        # the heap without changing membership — compare the SET here; the
        # exact-order fused contract is pinned above at rank='count'
        assert sorted((e.table_id, e.joinability) for e in r.results) == sorted(
            (e.table_id, e.joinability) for e in seq
        )
        assert r.stats.filter_matrix_bytes == 0


@pytest.mark.parametrize("bits", [128, 512])
def test_fused_engine_topk_across_widths(lake, bits):
    """Width sweep on the fused path: a 512-bit (16-lane) index runs the same
    fused kernel and still matches the scalar scan exactly."""
    corpus, _index, query, q_cols = lake
    index = MateIndex(corpus, cfg=xash.XashConfig(bits=bits))
    seq, _ = discovery.discover(index, query, q_cols, k=10)
    bat, st = discover_many(index, [(query, q_cols)], k=10, backend="fused")[0]
    assert [(e.table_id, e.joinability, e.mapping) for e in bat] == [
        (e.table_id, e.joinability, e.mapping) for e in seq
    ]
    assert st.filter_matrix_bytes == 0


def test_fused_distributed_filter_matches_broadcast():
    """impl='fused' sharded filter == the broadcast baseline (table and key
    counts), through shard_map + the interpret-mode Pallas launch."""
    import jax

    from repro.core import distributed

    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=60, seed=1))
    idx = MateIndex(corpus)
    queries = synthetic.make_mixed_queries(corpus, 1, 10, 2, seed=2)
    q, q_cols = queries[0]
    _keys, sk_of_key = discovery.build_query_superkeys(idx, q, q_cols)
    qsk = np.stack(list(sk_of_key.values()))
    row_tables = np.asarray(
        corpus.table_of_row(np.arange(corpus.total_rows)), dtype=np.int32
    )
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))
    sk, rt = distributed.shard_corpus_rows(
        idx.superkeys, row_tables, mesh, ("data",)
    )
    fn = distributed.make_distributed_filter(
        mesh, len(corpus.tables), ("data",), backend="fused"
    )
    tc, kc = fn(sk, rt, qsk)
    tc_ref, kc_ref = distributed.filter_counts_local(
        idx.superkeys, row_tables, qsk, len(corpus.tables)
    )
    assert np.array_equal(np.asarray(tc), np.asarray(tc_ref))
    assert np.array_equal(np.asarray(kc), np.asarray(kc_ref))


def test_fused_counts_from_real_superkeys():
    """End-to-end hash path: XASH superkeys (not random bits) through the
    fused kernel vs the materialised filter_match reduction."""
    cfg = xash.DEFAULT_CONFIG
    enc_r = RNG.integers(0, 38, size=(600, 5, 32)).astype(np.uint8)
    enc_q = RNG.integers(0, 38, size=(31, 2, 32)).astype(np.uint8)
    row_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(enc_r), cfg))
    q_sk = np.asarray(ref.xash_superkey_ref(jnp.asarray(enc_q), cfg))
    elig = _rand_elig(600, 31)
    seg = np.sort(RNG.integers(0, 11, 600)).astype(np.int32)
    got = ops.filter_table_counts(row_sk, q_sk, elig, seg, 11)
    match = np.asarray(ops.filter_match(row_sk, q_sk)) & elig.dense()
    want = np.bincount(seg, weights=match.sum(1), minlength=11).astype(np.int32)
    assert np.array_equal(got, want)
