"""Distributed MATE discovery: corpus sharded over the device mesh.

Both halves of the system shard the same way.  The ONLINE filtering layer
(the paper's hot loop) is embarrassingly parallel over candidate rows; the
OFFLINE build (``core.index.build_index``) is embarrassingly parallel over
unique values (hashing) and corpus rows (super keys, posting lists).  The
shard helpers at the bottom of this module (``shard_bounds``,
``mesh_shard_count``, ``pad_rows_to_shards``) are the
shared vocabulary: contiguous balanced row/value blocks, padded to the mesh
where device work needs equal shards.

For the online filter the natural large-scale layout is:

  * per-row super keys  uint32[n_rows, lanes]   → sharded over ALL mesh axes
    (rows are block-partitioned; a row's table never matters to the filter)
  * row→table ids       int32[n_rows]           → sharded identically
  * query super keys    uint32[n_keys, lanes]   → replicated
  * per-table candidate counts int32[n_tables]  → psum over row shards

A 512-chip pod-pair therefore filters ~512× the rows per step; the host-side
top-k logic (tiny) consumes the psum'ed per-table counts.  This module is the
dry-run/roofline target for the paper's own technique ("mate-filter" row in
EXPERIMENTS.md §Roofline).

Elastic scaling: the arrays are resharded by ``jax.device_put`` with a new
mesh — no host state depends on the mesh shape.  Straggler mitigation: row
blocks are balanced by construction (equal shard sizes after padding).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import registry
from repro.kernels.registry import Backend

_LOG = logging.getLogger(__name__)


def filter_counts_local(
    superkeys: jnp.ndarray,  # uint32[rows_local, lanes]
    row_tables: jnp.ndarray,  # int32[rows_local] (-1 for padding rows)
    query_sks: jnp.ndarray,  # uint32[n_keys, lanes]
    n_tables: int,
):
    """Per-table and per-key candidate counts for a local row shard."""
    conflict = query_sks[None, :, :] & ~superkeys[:, None, :]
    match = jnp.all(conflict == 0, axis=-1)  # [rows, keys]
    valid = (row_tables >= 0)[:, None]
    match = match & valid
    per_row = jnp.any(match, axis=-1).astype(jnp.int32)  # row matches ≥1 key
    table_counts = jnp.zeros((n_tables,), jnp.int32).at[
        jnp.maximum(row_tables, 0)
    ].add(per_row)
    key_counts = jnp.sum(match, axis=0, dtype=jnp.int32)  # [keys]
    return table_counts, key_counts


def filter_counts_local_blocked(
    superkeys: jnp.ndarray,
    row_tables: jnp.ndarray,
    query_sks: jnp.ndarray,
    n_tables: int,
    row_block: int = 1 << 16,
):
    """Memory-optimised probe: lane-unrolled (never materialises the
    [rows, keys, lanes] conflict tensor — peak is [block, keys] bool) and
    row-blocked via ``lax.map`` so HBM traffic is one streaming pass over the
    super keys (§Perf hillclimb 'mate-filter')."""
    lanes = superkeys.shape[1]
    n = superkeys.shape[0]
    nb = -(-n // row_block)
    pad = nb * row_block - n
    sk = jnp.pad(superkeys, ((0, pad), (0, 0)))
    rt = jnp.pad(row_tables, (0, pad), constant_values=-1)
    sk = sk.reshape(nb, row_block, lanes)
    rt = rt.reshape(nb, row_block)

    def block(args):
        skb, rtb = args
        ok = None
        for l in range(lanes):
            conflict_l = (query_sks[None, :, l] & ~skb[:, l : l + 1]) == 0
            ok = conflict_l if ok is None else (ok & conflict_l)
        ok = ok & (rtb >= 0)[:, None]
        per_row = jnp.any(ok, axis=-1).astype(jnp.int32)
        tc = jnp.zeros((n_tables,), jnp.int32).at[jnp.maximum(rtb, 0)].add(per_row)
        return tc, jnp.sum(ok, axis=0, dtype=jnp.int32)

    tcs, kcs = jax.lax.map(block, (sk, rt))
    return jnp.sum(tcs, axis=0), jnp.sum(kcs, axis=0)


def filter_counts_local_fused(
    superkeys: jnp.ndarray,
    row_tables: jnp.ndarray,
    query_sks: jnp.ndarray,
    n_tables: int,
):
    """Fused-kernel probe: the per-shard filter runs as ONE
    ``filter_kernel.filter_table_counts`` launch (mode='any'), so the
    [rows, keys] match tensor never exists per shard either — subsumption,
    the per-row any-reduction and the table-id scatter all happen in VMEM and
    only the two counts vectors leave the kernel.  Padding rows carry
    ``row_tables == -1`` (the kernel's own padding convention) and padded
    queries all-ones super keys (subsumed by nothing).  Above the kernel's
    table cap (the one-hot scatter tile is [block_n, tb] f32 in VMEM) the
    shard falls back to the lane-unrolled streaming impl."""
    from repro.kernels import filter_kernel

    interpret = jax.default_backend() != "tpu"
    n, lanes = superkeys.shape
    q = query_sks.shape[0]
    qb = max(-(-q // 128) * 128, 128)
    tb = max(-(-n_tables // 128) * 128, 128)
    if tb > filter_kernel.FUSED_MAX_TABLES:
        return filter_counts_local_blocked(
            superkeys, row_tables, query_sks, n_tables
        )
    block_n = filter_kernel.fused_block_n(tb)
    nb = max(-(-n // block_n) * block_n, block_n)
    sk = jnp.pad(superkeys, ((0, nb - n), (0, 0)))
    rt = jnp.pad(
        row_tables.astype(jnp.int32), (0, nb - n), constant_values=-1
    )
    qs = jnp.pad(
        query_sks, ((0, qb - q), (0, 0)),
        constant_values=np.uint32(0xFFFFFFFF),
    )
    counts, key_counts = filter_kernel.filter_table_counts(
        sk.T, qs.T, None, rt,
        n_tables=tb, n_queries=q, block_n=block_n, block_q=qb, mode="any",
        interpret=interpret,
    )
    return counts[:n_tables], key_counts[:q]


_FILTER_IMPLS = {
    "broadcast": filter_counts_local,
    "blocked": filter_counts_local_blocked,
    "fused": filter_counts_local_fused,
}

def shard_impl_for(backend: Backend | str | None, stats=None) -> str:
    """Map a resolved filter ``Backend`` onto a per-shard impl name.

    A shard-impl name ('broadcast' | 'blocked' | 'fused') passes through
    directly; a registry backend maps 'fused' -> the fused per-shard launch
    and every composed/host backend -> the broadcast baseline (the composed
    backends differ only in how the ENGINES consume the match matrix, which
    never exists per shard here).  None follows the registry precedence, so
    ``MATE_FILTER_BACKEND=fused`` and the TPU platform default select the
    fused shard launch without any caller plumbing.

    A 'fused-gather' backend DEMOTES to the fused shard impl here — and says
    so: this mesh row-filter API receives pre-gathered, pre-sharded superkey
    blocks, so there is no posting-list gather left to fuse.  The demotion is
    debug-logged and counted on ``stats`` (a ``DiscoveryStats``) when one is
    passed; the path that runs gather-fused WITHOUT demotion is the routed
    index (``core.routing.ShardedMateIndex``), whose per-shard epoch-pinned
    device stores give the gather kernel something shard-local to gather
    from.
    """
    if isinstance(backend, str) and backend in _FILTER_IMPLS:
        return backend
    bk = registry.resolve_backend(backend)
    if bk.gather:
        _LOG.debug(
            "shard_impl_for: demoting %r to the 'fused' shard impl — the"
            " mesh row filter takes pre-gathered superkey shards (use a"
            " routed ShardedMateIndex for shard-local gather-fused launches)",
            bk.name,
        )
        if stats is not None:
            stats.shard_gather_demotions += 1
        return "fused"
    return "fused" if bk.fused else "broadcast"


def make_distributed_filter(
    mesh: Mesh,
    n_tables: int,
    row_axes: tuple[str, ...],
    backend: Backend | str | None = None,
):
    """jit'd (superkeys, row_tables, query_sks) -> (table_counts, key_counts)
    with rows sharded over ``row_axes`` and outputs replicated (psum).

    ``backend`` is a resolved registry ``Backend``, a registered backend
    name, or a shard-impl name: 'broadcast' (baseline) | 'blocked'
    (lane-unrolled streaming) | 'fused' (single Pallas filter+segment-count
    launch per shard).  None resolves via the registry (env var, then
    platform default).  The pre-registry ``impl=`` kwarg was removed after
    its one-release deprecation window (PR 4): passing it raises TypeError.
    """
    impl = shard_impl_for(backend)
    local = _FILTER_IMPLS[impl]

    # pallas_call has no replication rule: the fused body skips the check
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(row_axes), P(row_axes), P()),
        out_specs=(P(), P()),
        check_vma=impl != "fused",
    )
    def _sharded(superkeys, row_tables, query_sks):
        tc, kc = local(superkeys, row_tables, query_sks, n_tables)
        tc = jax.lax.psum(tc, row_axes)
        kc = jax.lax.psum(kc, row_axes)
        return tc, kc

    return jax.jit(_sharded)


# ---------------------------------------------------------------------------
# Routed-index mesh filter (core.routing.ShardedMateIndex, mesh mode)
# ---------------------------------------------------------------------------


def _routed_local_counts_fn(
    mesh, row_axes, pad_items, qb, q, n_tables, impl: str,
):
    """Build the jitted shard_map'd routed filter for one shape bucket.

    Inputs (leading dim sharded over ``row_axes``, one block per shard):
      store  uint32[n_shards·pad_store, lanes] — per-shard superkey stores
      rows   int32[n_shards·pad_items]         — SHARD-LOCAL row offsets
      seg    int32[n_shards·pad_items]         — batch table ids (-1 pads)
      item   int32[n_shards·pad_items]         — item init-value ids (-1 pads)
      kval   int32[qb] (replicated)            — key init-value ids (-2 pads)
      qry    uint32[qb, fl] (replicated)       — query superkeys
    Output: int32[n_tables], psum'ed — per-table counts, replicated.

    Each shard gathers ONLY from its own store block and the single
    cross-shard exchange is the counts psum: superkey rows never leave
    their shard.  ``impl`` 'fused' runs the Pallas fused counts kernel per
    shard (mode='sum', ``n_tables`` within its scatter-tile cap); 'xla' is
    the lane-unrolled body for the composed and host backends —
    bit-identical counts either way.
    """
    from repro.kernels import filter_kernel

    def _local(store, rows, seg, item, kval, qry):
        fl = qry.shape[1]
        sk = store[rows][:, :fl]
        if impl == "fused":
            tb = max(-(-n_tables // 128) * 128, 128)
            block_n = min(pad_items, filter_kernel.fused_block_n(tb))
            block_q = min(qb, filter_kernel.DEFAULT_BLOCK_Q)
            counts, _ = filter_kernel.filter_table_counts(
                sk.T, qry.T, (item, kval), seg,
                n_tables=tb, n_queries=q, block_n=block_n, block_q=block_q,
                mode="sum", interpret=jax.default_backend() != "tpu",
            )
            counts = counts[:n_tables]
        else:
            ok = None
            for lane in range(fl):
                c = (qry[None, :, lane] & ~sk[:, lane : lane + 1]) == 0
                ok = c if ok is None else ok & c
            ok = ok & (item[:, None] == kval[None, :])
            per_row = jnp.sum(ok, axis=1).astype(jnp.int32)
            counts = (
                jnp.zeros((n_tables,), jnp.int32)
                .at[jnp.maximum(seg, 0)]
                .add(jnp.where(seg >= 0, per_row, 0))
            )
        return jax.lax.psum(counts, row_axes)

    # pallas_call has no replication rule: the fused body skips the check
    return jax.jit(
        jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=(
                P(row_axes), P(row_axes), P(row_axes), P(row_axes), P(), P()
            ),
            out_specs=P(),
            check_vma=impl != "fused",
        )
    )


def _routed_mesh_store(index):
    """The stacked equal-padded per-shard store blocks, device_put with the
    shard partitioning — cached on the tuple of PER-SHARD epochs, so a §5.4
    mutation on shard i re-uploads the stack once, lazily."""
    epochs = tuple(s.mutation_epoch for s in index.shards)
    cached = index._mesh_store_cache
    if cached is not None and cached[0] == epochs:
        return cached[1], cached[2]
    pad_store = max(max(s.n_rows for s in index.shards), 1)
    lanes = index.cfg.lanes
    stack = np.zeros((index.n_shards * pad_store, lanes), dtype=np.uint32)
    for i, s in enumerate(index.shards):
        stack[i * pad_store : i * pad_store + s.n_rows] = s.superkeys
    sharding = NamedSharding(index._mesh, P(index._row_axes))
    store = jax.device_put(stack, sharding)
    index._mesh_store_cache = (epochs, store, pad_store)
    return store, pad_store


def routed_filter_counts_mesh(
    index,
    rows: np.ndarray,
    query_sk: np.ndarray,
    elig,
    seg_ids: np.ndarray,
    n_tables: int,
    backend: Backend | str | None = None,
) -> np.ndarray:
    """The routed filter over ``index``'s mesh: int32[n_tables] counts,
    bit-identical to the host-routed (and single-host) counts.  ``elig`` is
    the batch's ``ops.Eligibility``.

    Each launch partitions its candidate items by owning shard, pads each
    shard's slice to a shared pow2 bucket, and runs the per-shard filter +
    counts psum as a single SPMD program.  Fused backends run the Pallas
    fused body, split into ``ops.table_chunks`` launches above its table
    cap; the others run the lane-unrolled XLA body.
    """
    from repro.kernels import ops

    impl = "fused" if registry.resolve_backend(backend).fused else "xla"
    return ops.chunked_counts(
        lambda r, e, s, nt: _routed_launch(index, r, query_sk, e, s, nt, impl),
        seg_ids, elig, n_tables, np.asarray(rows, dtype=np.int64),
    )


def _routed_launch(index, rows, query_sk, elig, seg_ids, n_tables, impl):
    """One shard_map launch of the routed filter (see
    ``routed_filter_counts_mesh``)."""
    from repro.kernels import ops

    mesh, row_axes = index._mesh, index._row_axes
    n_shards = index.n_shards
    q, fl = query_sk.shape
    sid = index._shard_ids_of_rows(rows)
    store, pad_store = _routed_mesh_store(index)

    per_shard = [np.nonzero(sid == s)[0] for s in range(n_shards)]
    max_items = max((len(ix) for ix in per_shard), default=0)
    pad_items = ops._bucket(max(max_items, 1), ops._FALLBACK_MIN_N)
    qb = ops._pow2_bucket(q, ops._FALLBACK_MIN_Q)

    rows_p = np.zeros(n_shards * pad_items, dtype=np.int32)
    seg_p = np.full(n_shards * pad_items, -1, dtype=np.int32)
    # eligibility ids: the items' packed per shard like the rows (all
    # padding to start with), the keys' replicated
    item_p, kval_p = elig[:0].padded(n_shards * pad_items, qb)
    for s, ix in enumerate(per_shard):
        if not len(ix):
            continue
        base = s * pad_items
        rows_p[base : base + len(ix)] = rows[ix] - index.shards[s].row_lo
        seg_p[base : base + len(ix)] = np.asarray(seg_ids)[ix]
        item_p[base : base + len(ix)] = elig.item_value[ix]
    qry_p = np.full((qb, fl), 0xFFFFFFFF, dtype=np.uint32)
    qry_p[:q] = query_sk

    key = (pad_store, pad_items, qb, q, fl, n_tables, impl)
    fn = index._mesh_filter_cache.get(key)
    if fn is None:
        fn = _routed_local_counts_fn(
            mesh, row_axes, pad_items, qb, q, n_tables, impl
        )
        index._mesh_filter_cache[key] = fn
    sharding = NamedSharding(mesh, P(row_axes))
    return np.asarray(
        fn(
            store,
            jax.device_put(rows_p, sharding),
            jax.device_put(seg_p, sharding),
            jax.device_put(item_p, sharding),
            jnp.asarray(kval_p),
            jnp.asarray(qry_p),
        )
    )


# ---------------------------------------------------------------------------
# Shard helpers shared by the online filter and the offline index build
# ---------------------------------------------------------------------------


def mesh_shard_count(mesh: Mesh, axes: tuple[str, ...]) -> int:
    """Number of shards a block-partition over ``axes`` produces."""
    return int(np.prod([mesh.shape[a] for a in axes]))


def shard_bounds(n: int, n_shards: int) -> np.ndarray:
    """int64[n_shards+1] contiguous balanced shard boundaries over ``n``
    items: shard ``i`` covers ``[bounds[i], bounds[i+1])``.

    Prefix shards take ``ceil(n / n_shards)`` items, trailing shards may be
    short or empty — the SAME contiguous-ascending layout a padded equal-size
    device partition induces, which is what makes the offline build's
    shard-merge order-preserving (shard outputs concatenate back into global
    row/value order).
    """
    size = -(-n // n_shards) if n else 0
    return np.minimum(
        np.arange(n_shards + 1, dtype=np.int64) * size, np.int64(n)
    )


def pad_rows_to_shards(x: np.ndarray, n_shards: int, value=0) -> np.ndarray:
    """Pad the leading dim up to an equal-shard multiple (≥ 1 row/shard)."""
    n = x.shape[0]
    target = max(-(-n // n_shards) * n_shards, n_shards)
    if target == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[0] = (0, target - n)
    return np.pad(x, pads, constant_values=value)


def shard_corpus_rows(
    superkeys: np.ndarray,
    row_tables: np.ndarray,
    mesh: Mesh,
    row_axes: tuple[str, ...],
):
    """Pad to shard multiple and device_put with the row sharding.

    Re-invoking with a different mesh is the elastic-scaling path: arrays are
    repartitioned from the host copy (or via d2d reshard when alive).
    """
    n_shards = mesh_shard_count(mesh, row_axes)
    sk = pad_rows_to_shards(np.asarray(superkeys, dtype=np.uint32), n_shards)
    rt = pad_rows_to_shards(
        np.asarray(row_tables, dtype=np.int32), n_shards, value=-1
    )
    sharding = NamedSharding(mesh, P(row_axes))
    return (
        jax.device_put(sk, sharding),
        jax.device_put(rt, sharding),
    )
