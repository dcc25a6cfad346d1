"""Ranked-discovery subsystem acceptance (ISSUE 9).

Pinned contracts:
  * profile build determinism — the build-time column profiles are
    byte-identical between the single-host pass, any host shard count, and
    the routed lake's per-shard stores (concatenated in shard order);
  * the profile gate is PURE PRUNING — with the gate on, the verified
    top-k SET is identical to the ungated run at every hash width, on
    deterministic lakes, crafted prunable tables, and (under hypothesis)
    randomly seeded lakes;
  * the scoring head's jitted launch matches its numpy oracle;
  * rank='quality' only REORDERS/annotates the count-ranked set — never
    changes membership — on the single-host and the routed index, and the
    serving tier inherits both knobs (cache hits replay quality entries
    exactly; fingerprints split by rank/gate so modes cannot cross-serve);
  * §5.4 mutations invalidate the profile store epoch-for-epoch (per shard
    on the routed lake), like the device superkey store;
  * stats plumbing is field-driven: ``DiscoveryStats.merge`` and
    ``SessionStats.absorb`` enumerate dataclass fields, so a newly added
    counter can never be silently dropped from aggregation.
"""

import dataclasses

import numpy as np
import pytest

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

from repro.core import profiles, ranking, xash
from repro.core.batched import discover_many
from repro.core.corpus import Corpus, Table
from repro.core.discovery import DiscoveryStats
from repro.core.index import build_index
from repro.core.routing import build_routed_index
from repro.core.session import (
    _ABSORBED,
    _NOT_AGGREGATED,
    DiscoveryConfig,
    MateSession,
    SessionStats,
)
from repro.data import synthetic
from repro.serve.cache import query_fingerprint
from repro.serve.engine import DiscoveryEngine

from conftest import ALL_BITS, ground_truth_lake, indexes_at_widths


@pytest.fixture(scope="module")
def lake():
    return ground_truth_lake(
        n_tables=60, corpus_seed=5, n_rows=25, key_width=2, query_seed=7
    )


@pytest.fixture(scope="module")
def built(lake):
    corpus, _q, _qc, _e = lake
    return indexes_at_widths(corpus)


def _key(entries):
    return [(e.table_id, e.joinability, e.mapping) for e in entries]


def _ids(entries):
    return {e.table_id for e in entries}


# ---------------------------------------------------------------------------
# Profile build: determinism + layout
# ---------------------------------------------------------------------------

def test_profile_build_deterministic_across_shard_counts(lake):
    """Single-host == any host shard count, byte for byte (the build_index
    sharded profile pass concatenates per-table-range parts)."""
    corpus, _q, _qc, _e = lake
    stores = {
        n: build_index(corpus, n_shards=n)[0].profiles() for n in (1, 2, 4)
    }
    assert profiles.profiles_equal(stores[1], stores[2])
    assert profiles.profiles_equal(stores[1], stores[4])


def test_profile_build_eager_matches_lazy_rebuild(lake):
    """build_index populates the store eagerly; a lazy rebuild from the
    same arenas (the post-mutation path) is byte-identical."""
    corpus, _q, _qc, _e = lake
    idx, stats = build_index(corpus)
    eager = idx.profiles()
    assert stats.profile_seconds >= 0 and stats.profile_bytes == eager.nbytes
    lazy = profiles.build_profiles(idx.corpus, idx.value_lanes, epoch=0)
    assert profiles.profiles_equal(eager, lazy)


def test_routed_per_shard_profiles_concat_to_single_host(lake):
    """The routed lake's shard-local stores, concatenated in shard order,
    are byte-identical to the single-host store — same determinism contract
    as the routed postings/superkeys."""
    corpus, _q, _qc, _e = lake
    single = build_index(corpus)[0].profiles()
    routed, rstats = build_routed_index(corpus, n_shards=3)
    parts = [routed._shard_profiles(s) for s in routed.shards]
    assert [p.epoch for p in parts] == [0, 0, 0]
    assert rstats.profile_bytes == sum(p.nbytes for p in parts)
    merged = profiles.merge_profiles(parts)
    assert profiles.profiles_equal(single, merged)


def test_profile_store_layout(lake):
    corpus, _q, _qc, _e = lake
    store = build_index(corpus)[0].profiles()
    nt = len(corpus.tables)
    assert store.n_tables == nt
    assert store.mask.shape == (nt, profiles.MASK_WORDS)
    assert store.sketch.shape == (nt, profiles.SKETCH_K)
    assert store.col_ptr[-1] == int(corpus.n_cols.sum())
    np.testing.assert_array_equal(store.n_cols, corpus.n_cols)
    np.testing.assert_array_equal(store.n_rows, np.diff(corpus.row_base))
    # cardinality is bounded by rows; every non-empty table has card >= 1
    assert (store.card_max <= store.n_rows).all()
    assert (store.card_max[store.n_rows > 0] >= 1).all()


# ---------------------------------------------------------------------------
# The gate is pure pruning
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", ALL_BITS)
def test_gate_is_pure_pruning_every_width(built, lake, bits):
    _corpus, query, q_cols, expected = lake
    idx = built[bits]
    base, _ = discover_many(idx, [(query, q_cols)], k=10)[0]
    gated, gstats = discover_many(
        idx, [(query, q_cols)], k=10, profile_gate=True
    )[0]
    assert _key(gated) == _key(base)  # count rank: order too
    assert gstats.tables_gated >= 0
    # the planted ground truth survives the gate
    assert set(expected) & _ids(gated) == set(expected) & _ids(base)


def test_gate_prunes_crafted_narrow_table(lake):
    """A planted 1-column table containing the query's init values is a
    candidate (its posting lists match) but can never host a width-2 key —
    the n_cols condition gates it deterministically."""
    corpus, query, q_cols, _e = lake
    init_vals = [row[q_cols[0]] for row in query.cells[:6]]
    tables = list(corpus.tables)
    narrow_id = len(tables)
    tables.append(Table(narrow_id, [[v] for v in init_vals]))
    corpus2 = Corpus(tables, max_len=corpus.max_len)
    idx = build_index(corpus2)[0]

    base, _ = discover_many(idx, [(query, q_cols)], k=10)[0]
    gated, gstats = discover_many(
        idx, [(query, q_cols)], k=10, profile_gate=True
    )[0]
    assert gstats.tables_gated >= 1
    assert gstats.gate_bytes_saved > 0
    assert _key(gated) == _key(base)
    # and the narrow table was among the gated (it cannot be in either set)
    keep = idx.gate_candidates(
        [tuple(row[c] for c in q_cols) for row in query.cells[:1]],
        np.asarray([narrow_id]),
    )
    assert not keep[0]


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_gate_purity_property(seed):
    """Hypothesis sweep: random lakes + random planted queries — the gated
    verified set always equals the ungated one (128/256/512 bits)."""
    corpus = synthetic.make_corpus(
        synthetic.SyntheticSpec(n_tables=25, seed=seed % 97)
    )
    query, q_cols, _exp, corpus = synthetic.make_query_with_ground_truth(
        corpus, n_rows=12, key_width=2, seed=seed
    )
    for bits in ALL_BITS:
        idx = build_index(corpus, cfg=xash.XashConfig(bits=bits))[0]
        base, _ = discover_many(idx, [(query, q_cols)], k=8)[0]
        gated, _ = discover_many(
            idx, [(query, q_cols)], k=8, profile_gate=True
        )[0]
        assert _key(gated) == _key(base)


# ---------------------------------------------------------------------------
# Scoring head: oracle parity + quality-rank set identity
# ---------------------------------------------------------------------------

def test_scoring_launch_matches_numpy_oracle():
    rng = np.random.default_rng(3)
    n, n_keys = 37, 14
    counts = rng.integers(0, 30, n).astype(np.float32)
    card = rng.integers(1, 50, n).astype(np.float32)
    rows = rng.integers(1, 60, n).astype(np.float32)
    q_sketch = rng.integers(0, 2**32, profiles.SKETCH_K, dtype=np.uint32)
    t_sketch = rng.integers(
        0, 2**32, (n, profiles.SKETCH_K), dtype=np.uint32
    )
    # force some sketch matches so the similarity term is exercised
    t_sketch[::3, :5] = q_sketch[:5]
    got = np.asarray(
        ranking._score_fn()(
            counts, np.float32(n_keys), card, rows, t_sketch, q_sketch
        )
    )
    want = ranking.score_np(
        counts, n_keys, card, rows,
        (t_sketch == q_sketch[None, :]).sum(axis=1),
    )
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert got.dtype == np.float32
    # (real profiles have card <= rows so scores land in [0, 1]; these raw
    # random inputs only pin launch/oracle parity, not the range)
    assert (got >= 0).all()


@pytest.mark.parametrize("bits", ALL_BITS)
def test_quality_rank_preserves_verified_set(built, lake, bits):
    _corpus, query, q_cols, _e = lake
    idx = built[bits]
    count_rank, _ = discover_many(idx, [(query, q_cols)], k=10)[0]
    quality, qstats = discover_many(
        idx, [(query, q_cols)], k=10, rank="quality", profile_gate=True
    )[0]
    assert _ids(quality) == _ids(count_rank)
    assert sorted(_key(quality)) == sorted(_key(count_rank))
    assert qstats.ranking_launches >= 1
    assert all(e.quality is not None for e in quality)
    # ordered by (-quality, -joinability, table_id)
    order = [(-e.quality, -e.joinability, e.table_id) for e in quality]
    assert order == sorted(order)
    # count-rank entries carry no annotation (and the default is unchanged)
    assert all(e.quality is None for e in count_rank)


def test_quality_rank_two_phase_matches_batched(built, lake):
    """A request scored inside a group of two gets the same
    quality-annotated entries as the same request alone — one scoring
    launch per request on the two-phase path."""
    _corpus, query, q_cols, _e = lake
    idx = built[128]
    solo, _ = discover_many(
        idx, [(query, q_cols)], k=10, rank="quality", profile_gate=True
    )[0]
    many = discover_many(
        idx, [(query, q_cols)] * 2, k=10, rank="quality", profile_gate=True
    )
    for entries, mstats in many:
        assert [(e.table_id, e.quality) for e in entries] == [
            (e.table_id, e.quality) for e in solo
        ]
        assert mstats.ranking_launches == 1


def test_routed_quality_matches_single_host(lake):
    """The routed lake inherits the whole subsystem: shard-local gate +
    shard-local profile features produce the exact single-host quality
    ordering (profiles are deterministic and the count merge is exact)."""
    corpus, query, q_cols, _e = lake
    single = MateSession.build(corpus, DiscoveryConfig(k=10))
    routed = MateSession.build(
        corpus, DiscoveryConfig(k=10), distributed=True, n_shards=3
    )
    ref, st_s = single.discover(query, q_cols)
    got, st_r = routed.discover(query, q_cols)
    assert _key(got) == _key(ref)
    assert [e.quality for e in got] == [e.quality for e in ref]
    assert st_r.tables_gated == st_s.tables_gated
    assert st_r.shard_launches > 0  # the filter really ran routed


# ---------------------------------------------------------------------------
# Serving inheritance
# ---------------------------------------------------------------------------

def test_serving_inherits_rank_and_gate(built, lake):
    _corpus, query, q_cols, _e = lake
    idx = built[128]
    session = MateSession(idx, DiscoveryConfig(k=10, result_cache=8))
    eng = DiscoveryEngine(session=session, batch=1)
    cold = eng.discover(query, q_cols)
    warm = eng.discover(query, q_cols)
    assert warm.from_cache and session.stats.cache_hits == 1
    assert _key(warm.results) == _key(cold.results)
    assert [e.quality for e in warm.results] == [
        e.quality for e in cold.results
    ]
    ref, _ = discover_many(
        idx, [(query, q_cols)], k=10, rank="quality", profile_gate=True
    )[0]
    assert _key(cold.results) == _key(ref)


def test_fingerprint_splits_by_rank_and_gate(lake):
    """A count-mode cache fill must never answer a quality-mode request:
    rank and gate are part of the query fingerprint."""
    _corpus, query, q_cols, _e = lake
    fps = {
        query_fingerprint(query, q_cols, rank=r, profile_gate=g)
        for r in ("count", "quality")
        for g in (False, True)
    }
    assert len(fps) == 4
    # and the default arguments reproduce the pre-ISSUE-9 fingerprint shape
    assert query_fingerprint(query, q_cols) == query_fingerprint(
        query, q_cols, rank="count", profile_gate=False
    )


def test_config_validates_rank():
    with pytest.raises(ValueError, match="rank"):
        DiscoveryConfig(rank="best")
    assert DiscoveryConfig().rank == "quality"
    assert DiscoveryConfig().profile_gate is True


# ---------------------------------------------------------------------------
# §5.4 mutations: epoch-pinned stores
# ---------------------------------------------------------------------------

def test_mutation_epoch_invalidates_profiles(lake):
    corpus, query, q_cols, _e = lake
    idx = build_index(
        Corpus([Table(t.table_id, [list(r) for r in t.cells]) for t in corpus.tables],
               max_len=corpus.max_len)
    )[0]
    s0 = idx.profiles()
    assert s0.epoch == 0 and idx.profiles() is s0  # stable while unmutated
    new_cells = [list(row[c] for c in q_cols) + ["x"] for row in query.cells]
    tid = idx.insert_table(new_cells)
    s1 = idx.profiles()
    assert s1 is not s0 and s1.epoch == idx.mutation_epoch
    assert s1.n_tables == s0.n_tables + 1
    # the inserted (joinable) table passes the gate against the query keys
    keys = list(
        dict.fromkeys(tuple(row[c] for c in q_cols) for row in query.cells)
    )
    assert idx.gate_candidates(keys, np.asarray([tid]))[0]
    # update: the store refreshes again (same discipline as device_store)
    idx.update_cell(tid, 0, 0, "zz-mutated")
    s2 = idx.profiles()
    assert s2 is not s1 and s2.epoch == idx.mutation_epoch


def test_routed_mutation_rebuilds_only_owning_shard(lake):
    corpus, _q, _qc, _e = lake
    fresh = Corpus(
        [Table(t.table_id, [list(r) for r in t.cells]) for t in corpus.tables],
        max_len=corpus.max_len,
    )
    routed, _ = build_routed_index(fresh, n_shards=2)
    before = [routed._shard_profiles(s) for s in routed.shards]
    victim = routed.shards[1].table_lo  # first table of shard 1
    routed.update_cell(victim, 0, 0, "routed-mutation")
    after = [routed._shard_profiles(s) for s in routed.shards]
    assert after[0] is before[0]  # shard 0 untouched
    assert after[1] is not before[1]
    assert after[1].epoch == routed.shards[1].mutation_epoch


# ---------------------------------------------------------------------------
# Field-driven stats plumbing
# ---------------------------------------------------------------------------

def test_discovery_stats_merge_covers_every_field():
    a, b = DiscoveryStats(), DiscoveryStats()
    for i, f in enumerate(dataclasses.fields(DiscoveryStats)):
        setattr(a, f.name, 2 * i + 1)
        setattr(b, f.name, 100 + i)
    out = a.merge(b)
    assert out is a
    for i, f in enumerate(dataclasses.fields(DiscoveryStats)):
        assert getattr(a, f.name) == (2 * i + 1) + (100 + i), f.name


def test_every_discovery_counter_is_classified_for_absorb():
    """The forgotten-field guard: every DiscoveryStats field is either
    absorbed into SessionStats or explicitly listed as not-aggregated —
    adding a counter without classifying it breaks this test."""
    names = {f.name for f in dataclasses.fields(DiscoveryStats)}
    assert set(_ABSORBED) | set(_NOT_AGGREGATED) == names
    assert not set(_ABSORBED) & set(_NOT_AGGREGATED)
    ss = SessionStats()
    for name in _ABSORBED:
        assert hasattr(ss, name), f"SessionStats lacks absorbed field {name}"


def test_absorb_raises_on_unmirrored_field(monkeypatch):
    """If a new DiscoveryStats counter is classified as absorbed but not
    mirrored on SessionStats, the very first absorb raises instead of
    silently dropping it."""
    from repro.core import session as session_mod

    monkeypatch.setattr(
        session_mod, "_ABSORBED", session_mod._ABSORBED + ("brand_new",)
    )
    ds = DiscoveryStats()
    ds.brand_new = 7  # simulate the newly added counter
    with pytest.raises(AttributeError):
        SessionStats().absorb(ds)


def test_absorb_accumulates_ranking_counters(built, lake):
    _corpus, query, q_cols, _e = lake
    session = MateSession(built[128], DiscoveryConfig(k=5))
    session.discover(query, q_cols)
    assert session.stats.ranking_launches >= 1
    assert session.stats.tables_gated >= 0
