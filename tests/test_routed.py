"""Routed lake (ISSUE 8): per-shard ownership, shard-local filter launches,
count-only merge — the routed-vs-single-host equivalence matrix.

The contract: a ``ShardedMateIndex`` at ANY shard count produces top-k
byte-identical to the single-host ``MateIndex`` at every width in
{128, 256, 512}, while the only bytes that cross a shard boundary are
int32 per-table count vectors (``DiscoveryStats.route_bytes_merged``) —
superkey rows never do.  The host-routed path (shards pinned to one
device) runs in every CI leg; the mesh-attached matrix runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the ``routed``
CI leg) and skips where fewer devices are visible.
"""

import numpy as np
import pytest

import jax

from repro.core import batched, discovery, xash
from repro.core.corpus import Corpus, Table
from repro.core.index import MateIndex
from repro.core.routing import (
    ShardedMateIndex,
    build_routed_index,
    table_aligned_bounds,
)
from repro.core.session import DiscoveryConfig, MateSession
from repro.data import synthetic
from repro.launch import mesh as meshlib
from repro.serve.engine import DiscoveryEngine

N_DEVICES = len(jax.devices())
SHARD_COUNTS = (1, 2, 4, 8)
WIDTHS = (128, 256, 512)

needs_8_devices = pytest.mark.skipif(
    N_DEVICES < max(SHARD_COUNTS),
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
    "(the routed CI leg)",
)


def topk_key(entries):
    return [(e.table_id, e.joinability, e.mapping) for e in entries]


@pytest.fixture(scope="module")
def lake():
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=60, seed=1))
    query, q_cols, _expected, corpus = synthetic.make_query_with_ground_truth(
        corpus
    )
    return corpus, query, q_cols


@pytest.fixture(scope="module")
def single_host(lake):
    corpus, _q, _qc = lake
    return {
        bits: MateIndex(
            corpus, cfg=xash.XashConfig(bits=bits), use_corpus_char_freq=True
        )
        for bits in WIDTHS
    }


def make_routed(corpus, bits, n_shards):
    return ShardedMateIndex(
        corpus,
        cfg=xash.XashConfig(bits=bits),
        use_corpus_char_freq=True,
        n_shards=n_shards,
    )


# ---------------------------------------------------------------------------
# Shard ownership geometry
# ---------------------------------------------------------------------------


def test_table_aligned_bounds_cover_and_align(lake):
    corpus, _q, _qc = lake
    for n in (1, 2, 3, 4, 8, 17):
        bounds = table_aligned_bounds(corpus.row_base, n)
        assert bounds[0] == 0 and bounds[-1] == corpus.total_rows
        assert np.all(np.diff(bounds) >= 0)
        # every interior bound sits ON a table boundary: no table is split
        interior = bounds[1:-1]
        assert np.all(np.isin(interior, corpus.row_base)), (n, interior)


def test_no_table_crosses_a_shard(lake):
    corpus, _q, _qc = lake
    idx = make_routed(corpus, 128, 4)
    for shard in idx.shards:
        tids = np.unique(
            np.asarray(
                corpus.table_of_row(np.arange(shard.row_lo, shard.row_hi))
            )
        )
        for other in idx.shards:
            if other.shard_id == shard.shard_id:
                continue
            o_tids = np.asarray(
                corpus.table_of_row(np.arange(other.row_lo, other.row_hi))
            )
            assert not np.intersect1d(tids, o_tids).size


# ---------------------------------------------------------------------------
# Routed-vs-single-host equivalence matrix (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", WIDTHS)
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_routed_matrix_byte_identical(lake, single_host, n_shards, bits):
    corpus, query, q_cols = lake
    idx = make_routed(corpus, bits, n_shards)
    ref = single_host[bits]
    want, _ = batched.discover_many(ref, [(query, q_cols)], k=10)[0]
    got, stats = batched.discover_many(idx, [(query, q_cols)], k=10)[0]
    assert topk_key(got) == topk_key(want)
    # the routed invariant: count vectors crossed shards, superkeys did not
    assert stats.shard_launches >= 1
    assert stats.route_bytes_merged > 0
    host_gather_bytes = stats.pl_items_checked * idx.cfg.lanes * 4
    if n_shards > 1:
        assert stats.route_bytes_merged < host_gather_bytes
    # sequential Algorithm 1 agrees too (it consumes the routed index
    # through fetch_postings/superkey_of_rows only)
    seq, _ = discovery.discover(idx, query, q_cols, k=10)
    assert topk_key(seq) == topk_key(want)


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_routed_artifact_parity(lake, single_host, n_shards):
    """fetch_postings / gather_candidates / superkey_of_rows reproduce the
    merged single-host artifacts exactly (shard concat == global order)."""
    corpus, _q, _qc = lake
    idx = make_routed(corpus, 128, n_shards)
    ref = single_host[128]
    values = [corpus.unique_values[i] for i in sorted(ref.postings)][:32]
    for v in values:
        assert np.array_equal(idx.fetch_postings(v), ref.fetch_postings(v)), v
    blk_got, blk_ref = idx.gather_candidates(values), ref.gather_candidates(
        values
    )
    assert np.array_equal(blk_got.table_ptr, blk_ref.table_ptr)
    assert np.array_equal(blk_got.table_ids, blk_ref.table_ids)
    assert np.array_equal(blk_got.rows, blk_ref.rows)
    assert np.array_equal(blk_got.value_idx, blk_ref.value_idx)
    rows = np.arange(0, corpus.total_rows, 3, dtype=np.int64)
    rng = np.random.default_rng(7)
    rng.shuffle(rows)  # out-of-order + cross-shard interleaved
    assert np.array_equal(idx.superkey_of_rows(rows), ref.superkey_of_rows(rows))


@pytest.mark.parametrize("bits", WIDTHS)
def test_routed_session_discover_many_identical(lake, single_host, bits):
    """Group batching (plan_and_count + score_from_counts) through a routed
    session matches the single-host session bit-for-bit, and the routed
    PlanCounts demux attributes launches/bytes per request."""
    corpus, query, q_cols = lake
    routed = MateSession.build(
        corpus, DiscoveryConfig(bits=bits), distributed=True, n_shards=4
    )
    assert getattr(routed.index, "routed", False)
    assert routed.build_stats is not None and routed.build_stats.sharded
    ref = MateSession(single_host[bits], DiscoveryConfig(bits=bits))
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 10, 2, seed=11
    )
    out = routed.discover_many(queries, k=[10, 4, 4])
    out_ref = ref.discover_many(queries, k=[10, 4, 4])
    for (entries, _), (entries_ref, _) in zip(out, out_ref):
        assert topk_key(entries) == topk_key(entries_ref)
    assert routed.stats.shard_launches > 0
    assert routed.stats.route_bytes_merged > 0
    # per-request attribution: the demux carries route accounting
    plans = routed.plan_and_count(queries)
    for pc in plans:
        if pc.plan.block.n_items:
            assert pc.route_launches >= 1
            assert pc.route_bytes == pc.route_launches * pc.counts.shape[0] * 4


def test_routed_bound_cache_replay_no_new_launches(lake):
    """score_from_counts(from_cache=True) must not re-count routed launches
    — the filter was paid for by the original request."""
    corpus, query, q_cols = lake
    routed = MateSession.build(
        corpus, DiscoveryConfig(bits=128), distributed=True, n_shards=2
    )
    (pc,) = routed.plan_and_count([(query, q_cols)])
    routed.score_from_counts(pc, k=10)
    launches = routed.stats.shard_launches
    bytes_merged = routed.stats.route_bytes_merged
    routed.score_from_counts(pc, k=5, from_cache=True)
    assert routed.stats.shard_launches == launches
    assert routed.stats.route_bytes_merged == bytes_merged


# ---------------------------------------------------------------------------
# Mesh-attached routing (the 8-virtual-device CI leg)
# ---------------------------------------------------------------------------


@needs_8_devices
@pytest.mark.parametrize("bits", WIDTHS)
@pytest.mark.parametrize("n_devices", SHARD_COUNTS)
def test_mesh_routed_matrix_byte_identical(lake, single_host, n_devices, bits):
    corpus, query, q_cols = lake
    want, _ = batched.discover_many(single_host[bits], [(query, q_cols)], k=10)[0]
    idx = make_routed(corpus, bits, n_devices)
    if n_devices > 1:
        mesh = meshlib.make_mesh((n_devices,), ("data",))
        idx.attach_mesh(mesh, ("data",))
    got, stats = batched.discover_many(idx, [(query, q_cols)], k=10)[0]
    assert topk_key(got) == topk_key(want)
    assert stats.shard_launches >= 1 and stats.route_bytes_merged > 0


@needs_8_devices
def test_mesh_built_routed_session(lake, single_host):
    """build_routed_index over a mesh: shard_map hashing + routed index,
    mesh stays attached, discovery identical."""
    corpus, query, q_cols = lake
    mesh = meshlib.make_mesh((4,), ("data",))
    idx, stats = build_routed_index(
        corpus,
        cfg=xash.XashConfig(bits=256),
        use_corpus_char_freq=True,
        mesh=mesh,
        row_axes=("data",),
    )
    assert stats.sharded and stats.n_shards == 4
    assert sum(stats.shard_rows) == corpus.total_rows
    want, _ = batched.discover_many(single_host[256], [(query, q_cols)], k=10)[0]
    got, _ = batched.discover_many(idx, [(query, q_cols)], k=10)[0]
    assert topk_key(got) == topk_key(want)
    # detach falls back to host-routed launches, still identical
    idx.detach_mesh()
    got2, st2 = batched.discover_many(idx, [(query, q_cols)], k=10)[0]
    assert topk_key(got2) == topk_key(want)
    assert st2.shard_launches >= 1


def test_attach_mesh_shard_mismatch_raises(lake):
    corpus, _q, _qc = lake
    idx = make_routed(corpus, 128, 2)
    if N_DEVICES < 1:
        pytest.skip("no devices")
    mesh = meshlib.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="shards"):
        idx.attach_mesh(mesh, ("data",))


def test_mesh_n_shards_conflict_raises(lake):
    corpus, _q, _qc = lake
    mesh = meshlib.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="n_shards"):
        build_routed_index(corpus, mesh=mesh, n_shards=3)


# ---------------------------------------------------------------------------
# §5.4 mutations stay shard-local (satellite 5)
# ---------------------------------------------------------------------------


def test_mutations_shard_local_epochs_and_stores():
    """insert/update/delete on a routed index bump ONLY the owning shard's
    epoch and refresh ONLY that shard's device store; top-k stays
    bit-identical to a from-scratch single-host rebuild."""
    corpus = synthetic.make_corpus(synthetic.SyntheticSpec(n_tables=60, seed=1))
    query, q_cols, _expected, corpus = synthetic.make_query_with_ground_truth(
        corpus
    )
    idx = make_routed(corpus, 128, 4)
    # materialise every shard's device store, remember identities
    for s in idx.shards:
        s.device_store()
    stores_before = [s._store for s in idx.shards]
    epochs_before = [s.mutation_epoch for s in idx.shards]
    agg_before = idx.mutation_epoch

    key_cells = [
        [query.cells[r][c] for c in q_cols] for r in range(query.n_rows)
    ]
    new_cells = [kc + ["routed-extra"] for kc in key_cells]
    tid = idx.insert_table(new_cells)  # appends to the LAST shard
    idx.update_cell(tid, 0, len(new_cells[0]) - 1, "mutated")

    epochs_after = [s.mutation_epoch for s in idx.shards]
    assert epochs_after[:-1] == epochs_before[:-1]  # untouched shards
    assert epochs_after[-1] > epochs_before[-1]  # owning shard bumped
    assert idx.mutation_epoch > agg_before  # aggregate is monotone
    # untouched shards' stores are the SAME objects (no re-upload)
    for s, store in zip(idx.shards[:-1], stores_before[:-1]):
        assert s.device_store() is store

    mutated = [list(r) for r in new_cells]
    mutated[0][-1] = "mutated"
    rebuilt = MateIndex(
        Corpus([*corpus.tables[:-1], Table(tid, mutated)]), cfg=idx.cfg
    )
    got, _ = batched.discover_many(idx, [(query, q_cols)], k=8)[0]
    want, _ = batched.discover_many(rebuilt, [(query, q_cols)], k=8)[0]
    assert topk_key(got) == topk_key(want)
    assert tid in [e.table_id for e in got]

    # delete stays shard-local too, and discovery drops the table
    epochs_mid = [s.mutation_epoch for s in idx.shards]
    idx.delete_table(tid)
    epochs_del = [s.mutation_epoch for s in idx.shards]
    assert epochs_del[:-1] == epochs_mid[:-1]
    assert epochs_del[-1] > epochs_mid[-1]
    ref = MateIndex(corpus2_without(corpus, tid), cfg=idx.cfg)
    got2, _ = batched.discover_many(idx, [(query, q_cols)], k=8)[0]
    want2, _ = batched.discover_many(ref, [(query, q_cols)], k=8)[0]
    assert topk_key(got2) == topk_key(want2)
    assert tid not in [e.table_id for e in got2]


def corpus2_without(corpus, tid):
    return Corpus([t for t in corpus.tables if t.table_id != tid])


def test_update_cell_on_interior_shard_touches_only_that_shard(lake):
    corpus, query, q_cols = lake
    idx = make_routed(corpus, 128, 4)
    for s in idx.shards:
        s.device_store()
    stores = [s._store for s in idx.shards]
    epochs = [s.mutation_epoch for s in idx.shards]
    # pick a table owned by shard 1 (an interior shard)
    shard = idx.shards[1]
    tid = int(shard.table_lo)
    assert idx.shard_of_table(tid).shard_id == 1
    old = corpus.tables[tid].cells[0][0]
    idx.update_cell(tid, 0, 0, old + "-touched")
    for i, s in enumerate(idx.shards):
        if i == 1:
            assert s.mutation_epoch > epochs[i]
            assert s.device_store() is not stores[i]
        else:
            assert s.mutation_epoch == epochs[i]
            assert s.device_store() is stores[i]
    # and the index still matches a rebuild
    rebuilt = MateIndex(Corpus(corpus.tables), cfg=idx.cfg)
    got, _ = batched.discover_many(idx, [(query, q_cols)], k=8)[0]
    want, _ = batched.discover_many(rebuilt, [(query, q_cols)], k=8)[0]
    assert topk_key(got) == topk_key(want)
    # restore for the module-scoped fixture's other consumers
    idx.update_cell(tid, 0, 0, old)


# ---------------------------------------------------------------------------
# Serving tier inherits routing (zero engine changes)
# ---------------------------------------------------------------------------


def test_serving_engine_over_routed_session(lake, single_host):
    corpus, query, q_cols = lake
    routed = MateSession.build(
        corpus,
        DiscoveryConfig(bits=128, result_cache=4),
        distributed=True,
        n_shards=4,
    )
    engine = DiscoveryEngine(session=routed, batch=4)
    queries = [(query, q_cols)] + synthetic.make_mixed_queries(
        corpus, 2, 10, 2, seed=11
    )
    reqs = [engine.submit(q, qc) for q, qc in queries]
    served = engine.flush()
    assert len(served) == len(queries)
    assert all(r.done for r in reqs)
    ref = MateSession(single_host[128], DiscoveryConfig(bits=128))
    for (q, qc), req in zip(queries, reqs):
        want, _ = ref.discover(q, qc, k=routed.config.k)
        assert topk_key(req.results) == topk_key(want)
    assert routed.stats.shard_launches > 0
    # repeat traffic answers from the result cache (mutation_epoch-keyed)
    hit = engine.discover(query, q_cols)
    assert hit.from_cache
    # a shard-local mutation invalidates it (aggregate epoch moved)
    routed.insert_table([["cache", "buster"]])
    miss = engine.discover(query, q_cols)
    assert not miss.from_cache
