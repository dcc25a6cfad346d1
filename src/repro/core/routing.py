"""Routed multi-host index: per-shard ownership + count-merge query routing.

PR 5's sharded build still merged every shard's postings back onto one host
and PR 7's device store served from one host's memory — fine for one box,
the hard ceiling for a billion-value lake (ROADMAP item 1).  This module
keeps each shard's state RESIDENT where it was built and routes queries to
the data instead:

  * ``MateShard`` — one shard's postings, CSR payload, superkey slice and
    epoch-pinned device store.  Shards own contiguous ascending row ranges
    (the ``merge_shard_postings`` contract), SNAPPED TO TABLE BOUNDARIES so
    every table is wholly owned by exactly one shard.
  * ``ShardedMateIndex`` — duck-types ``MateIndex`` for the engines and the
    serving tier, but holds NO global superkey array and NO global device
    store.  The §6.3 filter runs as shard-local counts-only launches
    (``ops.gather_filter_table_counts`` against each shard's own store, or
    the fused/host fallbacks), and only per-table count vectors are merged
    across shards.  Phase-B verification re-gathers surviving tables'
    superkey slices from the owning shard only.  §5.4 mutations apply
    shard-locally: per-shard ``mutation_epoch``, so an update refreshes one
    shard's device store, never the lake's.

The routed invariant (pinned by ``tests/test_routed.py``): NO superkey row
ever crosses a shard boundary on the filter path — the cross-shard traffic
is exactly ``DiscoveryStats.route_bytes_merged`` bytes of int32 counts
(compare with the ``n_items × lanes × 4`` superkey bytes a host-gather
design ships), over ``DiscoveryStats.shard_launches`` launches.

Table-aligned ownership is what makes the count merge exact: a candidate
table's rows all live on one shard, so per-table counts from different
shards never partially overlap — the merge is a plain sum (the all-reduce
the mesh mode runs as ``jax.lax.psum``), bit-identical to the single-host
counts vector.

Mesh mode (``attach_mesh``): the same shard-local filter runs as ONE
``shard_map`` launch over the per-shard store blocks with the count merge
as an in-program ``psum`` (``core.distributed.make_routed_filter``); without
a mesh the shards launch host-routed, one per owning shard, each against its
own (optionally per-device) resident store.  Both modes produce the same
counts, and both keep superkey rows shard-local.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

from repro import telemetry
from repro.core import profiles as profiles_lib
from repro.core import xash
from repro.core.corpus import Corpus, Table
from repro.core.index import (
    BuildStats,
    MateIndex,
    _aggregate_superkeys,
    _csr_ptr,
    _hash_unique_values,
    _intern_value,
    _postings_dict,
    _resolve_cfg,
    _shard_postings,
)
from repro.kernels import ops, registry
from repro.kernels.registry import Backend

_LOG = logging.getLogger(__name__)


def table_aligned_bounds(row_base: np.ndarray, n_shards: int) -> np.ndarray:
    """int64[n_shards+1] contiguous row bounds over ``row_base`` tables,
    balanced like ``distributed.shard_bounds`` but SNAPPED UP to the next
    table boundary — every table's rows land wholly inside one shard.

    Whole-table ownership is the routing contract: per-table candidate
    counts then come from exactly one shard each, so the cross-shard count
    merge is an exact sum (non-owning shards contribute zero) and phase-B
    verification re-gathers any surviving table from a single shard.
    """
    from repro.core import distributed

    row_base = np.asarray(row_base, dtype=np.int64)
    total = int(row_base[-1])
    ideal = distributed.shard_bounds(total, n_shards)
    bounds = np.zeros(n_shards + 1, dtype=np.int64)
    for i in range(1, n_shards):
        t = int(np.searchsorted(row_base, ideal[i], side="left"))
        t = min(t, len(row_base) - 1)
        bounds[i] = max(int(row_base[t]), int(bounds[i - 1]))
    bounds[n_shards] = total
    return bounds


@dataclasses.dataclass
class MateShard:
    """One shard's resident state: rows [row_lo, row_hi) of the corpus —
    whole tables [table_lo, table_hi) — with the shard's own superkey slice,
    posting lists (GLOBAL row ids, shard-local membership) and an
    epoch-pinned device store.  Mutations bump ``_mutations`` (this shard's
    epoch) only; other shards' stores stay untouched."""

    shard_id: int
    row_lo: int
    row_hi: int
    table_lo: int
    table_hi: int
    superkeys: np.ndarray  # uint32[row_hi-row_lo, lanes]
    postings: dict[int, np.ndarray]  # value id -> int64[m, 2] (global row, col)
    device: object | None = None  # jax device pinning this shard's store
    _mutations: int = 0
    _store: object = None
    _store_epoch: int = -1
    _deleted_tables: set = dataclasses.field(default_factory=set)
    _deleted_mask: np.ndarray | None = None
    _deleted_mask_epoch: int = -1
    # this shard's column-profile store (ranking subsystem), epoch-pinned to
    # THIS shard's mutations exactly like the device store
    _profiles: object = None

    @property
    def n_rows(self) -> int:
        return self.row_hi - self.row_lo

    @property
    def mutation_epoch(self) -> int:
        """Monotonic count of §5.4 mutations applied TO THIS SHARD."""
        return self._mutations

    def owns_table(self, table_id: int) -> bool:
        return self.table_lo <= table_id < self.table_hi

    def device_store(self):
        """This shard's device-resident superkey store, re-uploaded lazily
        when (and only when) THIS shard's mutation epoch moved — the
        per-shard counterpart of ``MateIndex.device_store``."""
        if self._store is None or self._store_epoch != self._mutations:
            from repro.kernels import ops

            self._store = ops.device_store(self.superkeys, self.device)
            self._store_epoch = self._mutations
        return self._store


class ShardedMateIndex:
    """Routed multi-shard index, duck-typing ``MateIndex`` for the engines.

    The engines detect the routed path via the ``routed`` class attribute
    and divert their filter launches to ``routed_counts`` BEFORE touching
    any global-array surface (there is none here: superkeys live per shard).
    Everything row-free — query-key hashing, candidate CSR assembly, the
    Algorithm 1 visit order — reuses ``MateIndex``'s own methods unchanged,
    so the two index types cannot drift apart on query semantics.
    """

    routed = True

    def __init__(
        self,
        corpus: Corpus,
        cfg: xash.XashConfig = xash.DEFAULT_CONFIG,
        hash_name: str = "xash",
        use_corpus_char_freq: bool = False,
        n_shards: int = 2,
        devices: list | None = None,
    ):
        cfg = _resolve_cfg(corpus, cfg, hash_name, use_corpus_char_freq)
        value_lanes = _hash_unique_values(
            corpus.unique_values, corpus.unique_enc, cfg, hash_name,
            corpus.avg_row_width(),
        )
        self._init_from_parts(
            corpus, cfg, hash_name, value_lanes, n_shards, devices
        )

    def _init_from_parts(
        self, corpus, cfg, hash_name, value_lanes, n_shards, devices=None
    ) -> None:
        """Shared constructor tail: per-shard superkeys + postings from the
        replicated value-hash arena (``build_routed_index`` seam)."""
        self.corpus = corpus
        self.cfg = cfg
        self.hash_name = hash_name
        self.value_lanes = value_lanes
        n_shards = max(int(n_shards), 1)
        n_values = len(corpus.unique_values)
        bounds = table_aligned_bounds(corpus.row_base, n_shards)
        table_bounds = np.searchsorted(corpus.row_base, bounds)
        if devices is None:
            import jax

            devices = jax.devices()
        self.shards: list[MateShard] = []
        for i in range(n_shards):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            payload, counts = _shard_postings(
                corpus.cell_value_ids, lo, hi, n_values
            )
            self.shards.append(
                MateShard(
                    shard_id=i,
                    row_lo=lo,
                    row_hi=hi,
                    table_lo=int(table_bounds[i]),
                    table_hi=int(table_bounds[i + 1]),
                    superkeys=_aggregate_superkeys(
                        corpus.cell_value_ids[lo:hi], value_lanes, cfg.lanes
                    ),
                    postings=_postings_dict(payload, _csr_ptr(counts)),
                    device=devices[i % len(devices)] if devices else None,
                )
            )
        self._mesh = None
        self._row_axes = None
        self._mesh_filter_cache: dict = {}
        self._mesh_store_cache: tuple | None = None

    @classmethod
    def _from_build(
        cls, corpus, cfg, hash_name, value_lanes, n_shards, devices=None
    ) -> "ShardedMateIndex":
        """Assemble from a prebuilt (possibly mesh-hashed) value arena —
        the ``build_routed_index`` seam.  ``cfg`` must be resolved."""
        self = cls.__new__(cls)
        self._init_from_parts(
            corpus, cfg, hash_name, value_lanes, n_shards, devices
        )
        return self

    # -- MateIndex duck-type surface (row-free paths reused verbatim) -------

    hash_values = MateIndex.hash_values
    superkey_of_keys = MateIndex.superkey_of_keys
    gather_candidates = MateIndex.gather_candidates

    @property
    def bits(self) -> int:
        return self.cfg.bits

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def shard_row_bounds(self) -> np.ndarray:
        """int64[n_shards+1] — the contiguous ascending ownership bounds."""
        return np.asarray(
            [self.shards[0].row_lo] + [s.row_hi for s in self.shards],
            dtype=np.int64,
        )

    @property
    def mutation_epoch(self) -> int:
        """Aggregate §5.4 epoch: the SUM of per-shard epochs — monotonic, so
        everything keyed on it (serve caches, ``PlanCounts.epoch``)
        invalidates exactly when any shard changed.  Per-shard staleness
        (which store actually re-uploads) is tracked per shard."""
        return sum(s.mutation_epoch for s in self.shards)

    def shard_of_table(self, table_id: int) -> MateShard:
        """The one shard owning ``table_id`` (whole-table ownership)."""
        rb = int(self.corpus.row_base[table_id])
        return self.shards[self._shard_ids_of_rows(np.asarray([rb]))[0]]

    def _shard_ids_of_rows(self, global_rows: np.ndarray) -> np.ndarray:
        bounds = self.shard_row_bounds
        sid = np.searchsorted(bounds, np.asarray(global_rows), side="right") - 1
        return np.clip(sid, 0, len(self.shards) - 1).astype(np.int64)

    # -- lookups ------------------------------------------------------------

    def fetch_postings(self, value: str) -> np.ndarray:
        """PL items for a value, shard-merged: int64[n, 2] (global row, col).

        Shards cover contiguous ascending row ranges, so concatenating their
        per-value slices in shard order IS the global row-major PL order —
        the ``merge_shard_postings`` argument, applied at fetch time instead
        of build time.  Bit-identical to ``MateIndex.fetch_postings``.
        """
        vid = self.corpus.value_of.get(value)
        if vid is None:
            return np.zeros((0, 2), dtype=np.int64)
        parts = []
        for s in self.shards:
            pl = s.postings.get(vid)
            if pl is None:
                continue
            if s._deleted_tables:
                pl = pl[~self._shard_deleted_mask(s)[pl[:, 0] - s.row_lo]]
            if len(pl):
                parts.append(pl)
        if not parts:
            return np.zeros((0, 2), dtype=np.int64)
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def _shard_deleted_mask(self, shard: MateShard) -> np.ndarray:
        """Shard-local tombstone row mask, epoch-cached on the SHARD."""
        if shard._deleted_mask_epoch != shard._mutations:
            mask = np.zeros(shard.n_rows, dtype=bool)
            rb = self.corpus.row_base
            for t in shard._deleted_tables:
                mask[int(rb[t]) - shard.row_lo : int(rb[t + 1]) - shard.row_lo] = True
            shard._deleted_mask = mask
            shard._deleted_mask_epoch = shard._mutations
        return shard._deleted_mask

    def superkey_of_rows(self, global_rows: np.ndarray) -> np.ndarray:
        """Routed block gather: each row's superkey comes from its OWNING
        shard's slice — the phase-B verification re-gather.  Surviving
        tables are wholly owned, so a table's slice touches one shard."""
        rows = np.asarray(global_rows, dtype=np.int64)
        out = np.empty((rows.shape[0], self.cfg.lanes), dtype=np.uint32)
        if rows.shape[0] == 0:
            return out
        sid = self._shard_ids_of_rows(rows)
        for s in np.unique(sid):
            shard = self.shards[int(s)]
            m = sid == s
            out[m] = shard.superkeys[rows[m] - shard.row_lo]
        return out

    # -- column profiles (ranking subsystem), shard-local -------------------

    def _shard_ids_of_tables(self, table_ids: np.ndarray) -> np.ndarray:
        """Owning shard id per table (whole-table ownership, vectorised)."""
        his = np.asarray([s.table_hi for s in self.shards], dtype=np.int64)
        sid = np.searchsorted(his, np.asarray(table_ids), side="right")
        return np.clip(sid, 0, len(self.shards) - 1).astype(np.int64)

    def _shard_profiles(self, shard: MateShard) -> profiles_lib.ProfileStore:
        """The shard's own ``ProfileStore`` over its tables [table_lo,
        table_hi), rebuilt lazily when THIS shard's §5.4 epoch moved — the
        per-shard counterpart of ``MateIndex.profiles`` (and the same
        refresh discipline as ``MateShard.device_store``)."""
        if (
            shard._profiles is None
            or shard._profiles.epoch != shard._mutations
        ):
            shard._profiles = profiles_lib.build_profiles(
                self.corpus, self.value_lanes,
                shard.table_lo, shard.table_hi,
                epoch=shard._mutations,
            )
        return shard._profiles

    def gate_candidates(
        self, distinct_keys: list[tuple[str, ...]], table_ids: np.ndarray
    ) -> np.ndarray:
        """Routed profile gate: the query's gate inputs are computed once,
        each candidate table is gated against its OWNING shard's profile
        store — no profile bytes cross shards, matching the filter-path
        routing contract.  Same keep-mask as the single-host gate."""
        ids = np.asarray(table_ids, dtype=np.int64)
        keep = np.ones(ids.shape[0], dtype=bool)
        if ids.shape[0] == 0 or not distinct_keys:
            return keep
        kvi, probe, len_bucket, vclass = profiles_lib.query_gate_inputs(
            distinct_keys, self.hash_values
        )
        width = len(distinct_keys[0])
        sid = self._shard_ids_of_tables(ids)
        for s in np.unique(sid):
            shard = self.shards[int(s)]
            m = sid == s
            keep[m] = profiles_lib.gate_tables(
                self._shard_profiles(shard), ids[m] - shard.table_lo,
                kvi, probe, len_bucket, vclass, width,
            )
        return keep

    def profile_features(
        self, table_ids: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Scoring-head feature gather, each row from its owning shard's
        store (``MateIndex.profile_features`` routed counterpart)."""
        ids = np.asarray(table_ids, dtype=np.int64)
        n = ids.shape[0]
        card = np.zeros(n, dtype=np.int32)
        rows = np.zeros(n, dtype=np.int32)
        sketch = np.zeros((n, profiles_lib.SKETCH_K), dtype=np.uint32)
        if n == 0:
            return card, rows, sketch
        sid = self._shard_ids_of_tables(ids)
        for s in np.unique(sid):
            shard = self.shards[int(s)]
            m = sid == s
            store = self._shard_profiles(shard)
            local = ids[m] - shard.table_lo
            card[m] = store.card_max[local]
            rows[m] = store.n_rows[local]
            sketch[m] = store.sketch[local]
        return card, rows, sketch

    # -- the routed filter --------------------------------------------------

    def attach_mesh(self, mesh, row_axes: tuple[str, ...] | None = None) -> None:
        """Run the routed filter as ONE ``shard_map`` launch over the mesh
        (count merge = in-program ``psum``) instead of host-routed per-shard
        launches.  The mesh's shard count must equal ``n_shards`` — shard i's
        store block lives on mesh slot i, so ownership and placement agree.
        """
        from repro.core import distributed

        row_axes = tuple(row_axes or mesh.axis_names)
        n = distributed.mesh_shard_count(mesh, row_axes)
        if n != self.n_shards:
            raise ValueError(
                f"mesh shards ({n} over axes {row_axes}) must match index"
                f" shards ({self.n_shards})"
            )
        self._mesh = mesh
        self._row_axes = row_axes
        self._mesh_filter_cache.clear()
        self._mesh_store_cache = None

    def detach_mesh(self) -> None:
        self._mesh = None
        self._row_axes = None
        self._mesh_filter_cache.clear()
        self._mesh_store_cache = None

    def routed_counts(
        self,
        rows: np.ndarray,
        query_sk: np.ndarray,
        elig: ops.Eligibility,
        seg_ids: np.ndarray,
        n_tables: int,
        *,
        backend: Backend | str | None = None,
        fused_block_n: int | None = None,
        stats=None,
    ) -> np.ndarray:
        """Per-table eligible-hit counts for one batch, computed WHERE THE
        ROWS LIVE: one counts-only launch per owning shard against that
        shard's resident store, merged by summation.  Bit-identical to the
        single-host counts (whole-table ownership: each table's count comes
        from exactly one shard; the others contribute zero).

        ``stats`` (a ``DiscoveryStats``) receives the routed accounting:
        ``shard_launches``, ``route_bytes_merged`` (the ONLY cross-shard
        bytes), ``filter_fused_launches``/``gather_bytes_saved`` for the
        launches that ran fused/gather-fused, and ``shard_gather_demotions``
        (+ a debug log) when a gather-capable backend had to demote.
        """
        bk = registry.resolve_backend(backend)
        counts = np.zeros(n_tables, dtype=np.int32)
        rows = np.asarray(rows, dtype=np.int64)
        n, q = rows.shape[0], query_sk.shape[0]
        if n == 0 or q == 0 or n_tables == 0:
            return counts
        if self._mesh is not None and self.n_shards > 1:
            return self._routed_counts_mesh(
                rows, query_sk, elig, seg_ids, n_tables, bk, stats
            )
        sid = self._shard_ids_of_rows(rows)
        for s in np.unique(sid):
            shard = self.shards[int(s)]
            m = sid == s
            local = rows[m] - shard.row_lo
            elig_s = elig[m]
            seg_s = np.asarray(seg_ids)[m]
            c = self._shard_counts(
                shard, local, query_sk, elig_s, seg_s, n_tables, bk,
                fused_block_n, stats,
            )
            counts += c
            if stats is not None:
                stats.shard_launches += 1
                # the merge ships this shard's counts vector — nothing else
                stats.route_bytes_merged += int(c.nbytes)
        return counts

    def _shard_counts(
        self, shard, local, query_sk, elig_s, seg_s, n_tables, bk,
        fused_block_n, stats,
    ) -> np.ndarray:
        """One shard-local counts-only launch (gather-fused → fused → host)."""
        fl = query_sk.shape[1]
        if bk.gather and ops.gather_store_fits(shard.superkeys):
            # counts alone: scoring re-gathers from the owning shard, so the
            # launch's surviving pairs are never read back
            with telemetry.span("filter.launch"):
                c = ops.chunked_counts(
                    lambda r, e, s, nt: ops.gather_filter_table_counts(
                        shard.device_store(), r, query_sk, e, s, nt,
                        block_n=fused_block_n,
                    ),
                    seg_s, elig_s, n_tables, local,
                )
            if stats is not None:
                stats.filter_fused_launches += 1
                stats.gather_bytes_saved += int(local.shape[0]) * (fl * 4 - 4)
            return c
        if bk.gather:
            _LOG.debug(
                "routed shard %d: demoting fused-gather (store %d bytes) to"
                " the host-gather fused launch",
                shard.shard_id, shard.superkeys.nbytes,
            )
            if stats is not None:
                stats.shard_gather_demotions += 1
        row_sk = shard.superkeys[local][:, :fl]
        if bk.fused:
            _, c = ops.filter_hits_table_counts(
                row_sk, query_sk, elig_s, seg_s, n_tables, backend="fused",
                fused_block_n=fused_block_n,
            )
            if stats is not None:
                stats.filter_fused_launches += 1
            return c
        # composed/host backends: counts-only by construction — the
        # shard-local matrix never leaves the shard.
        hits = ops.subsume_np(row_sk, query_sk) & elig_s.dense()
        return np.bincount(
            np.asarray(seg_s, dtype=np.int64),
            weights=hits.sum(axis=1),
            minlength=n_tables,
        ).astype(np.int32)[:n_tables]

    def _routed_counts_mesh(
        self, rows, query_sk, elig, seg_ids, n_tables, bk, stats
    ) -> np.ndarray:
        """Mesh mode: ONE shard_map launch, per-shard filter + psum merge."""
        from repro.core import distributed

        counts = distributed.routed_filter_counts_mesh(
            self, rows, query_sk, elig, seg_ids, n_tables, bk
        )
        if stats is not None:
            stats.shard_launches += self.n_shards
            stats.route_bytes_merged += int(counts.nbytes) * self.n_shards
            if bk.fused:
                stats.filter_fused_launches += self.n_shards
        return counts

    # -- index updates (§5.4), applied shard-locally ------------------------

    def insert_table(self, cells: list[list[str]], name: str = "") -> int:
        """Append a table to the LAST shard (preserves contiguous ascending
        ownership) — only that shard's epoch bumps, so only its device store
        re-uploads; every other shard's resident state is untouched."""
        corpus = self.corpus
        shard = self.shards[-1]
        shard._mutations += 1
        table = Table(table_id=len(corpus.tables), cells=cells, name=name)
        n_rows, n_cols = table.n_rows, table.n_cols
        if n_cols > corpus.max_cols:
            corpus.cell_value_ids = np.pad(
                corpus.cell_value_ids,
                ((0, 0), (0, n_cols - corpus.max_cols)),
                constant_values=-1,
            )
            corpus.max_cols = n_cols
        corpus.tables.append(table)
        corpus.row_base = np.append(corpus.row_base, corpus.row_base[-1] + n_rows)
        corpus.n_cols = np.append(corpus.n_cols, n_cols)
        base = corpus.total_rows
        corpus.total_rows += n_rows

        new_ids = np.full((n_rows, corpus.max_cols), -1, dtype=np.int32)
        for r, row in enumerate(cells):
            for c, v in enumerate(row):
                new_ids[r, c] = _intern_value(self, v)
        corpus.cell_value_ids = np.concatenate([corpus.cell_value_ids, new_ids])
        new_sk = _aggregate_superkeys(new_ids, self.value_lanes, self.cfg.lanes)
        shard.superkeys = np.concatenate([shard.superkeys, new_sk])
        shard.row_hi += n_rows
        shard.table_hi += 1
        for r in range(n_rows):
            for c in range(len(cells[r])):
                vid = int(new_ids[r, c])
                item = np.array([[base + r, c]], dtype=np.int64)
                shard.postings[vid] = (
                    np.concatenate([shard.postings[vid], item])
                    if vid in shard.postings
                    else item
                )
        return table.table_id

    def delete_table(self, table_id: int) -> None:
        """Tombstone on the OWNING shard only (its epoch, its store)."""
        shard = self.shard_of_table(table_id)
        shard._mutations += 1
        shard._deleted_tables.add(table_id)
        lo = int(self.corpus.row_base[table_id]) - shard.row_lo
        hi = int(self.corpus.row_base[table_id + 1]) - shard.row_lo
        shard.superkeys[lo:hi] = 0

    def update_cell(self, table_id: int, row: int, col: int, value: str) -> None:
        """Update one cell: postings swap + row re-hash, all on the owning
        shard — the other shards' epochs (and device stores) do not move."""
        corpus = self.corpus
        shard = self.shard_of_table(table_id)
        shard._mutations += 1
        grow = int(corpus.row_base[table_id]) + row
        old_vid = int(corpus.cell_value_ids[grow, col])
        vid = _intern_value(self, value)
        corpus.tables[table_id].cells[row][col] = value
        corpus.cell_value_ids[grow, col] = vid
        if old_vid in shard.postings:
            pl = shard.postings[old_vid]
            keep = ~((pl[:, 0] == grow) & (pl[:, 1] == col))
            shard.postings[old_vid] = pl[keep]
        item = np.array([[grow, col]], dtype=np.int64)
        shard.postings[vid] = (
            np.concatenate([shard.postings[vid], item])
            if vid in shard.postings
            else item
        )
        shard.superkeys[grow - shard.row_lo] = _aggregate_superkeys(
            corpus.cell_value_ids[grow : grow + 1], self.value_lanes,
            self.cfg.lanes,
        )[0]

    def __repr__(self) -> str:
        return (
            f"ShardedMateIndex(shards={self.n_shards}, "
            f"rows={self.corpus.total_rows}, bits={self.bits}, "
            f"mesh={'attached' if self._mesh is not None else 'none'})"
        )


def build_routed_index(
    corpus: Corpus,
    cfg: xash.XashConfig = xash.DEFAULT_CONFIG,
    hash_name: str = "xash",
    use_corpus_char_freq: bool = False,
    *,
    n_shards: int | None = None,
    mesh=None,
    row_axes: tuple[str, ...] | None = None,
    devices: list | None = None,
) -> tuple[ShardedMateIndex, BuildStats]:
    """Offline phase for the ROUTED lake: same sharded passes as
    ``core.index.build_index`` (mesh-sharded unique-value hashing when a
    mesh is given), but per-shard artifacts are NEVER merged — each shard
    keeps its postings/superkeys resident and the index routes to them.
    ``BuildStats.merge_seconds`` is therefore structurally zero here.

    With a ``mesh``, ``n_shards`` defaults to the mesh shard count and the
    returned index comes with the mesh ATTACHED (shard_map filter mode).
    """
    t_start = time.perf_counter()
    cfg = _resolve_cfg(corpus, cfg, hash_name, use_corpus_char_freq)
    from repro.core import distributed

    mesh_shards = 0
    if mesh is not None:
        row_axes = tuple(row_axes or mesh.axis_names)
        mesh_shards = distributed.mesh_shard_count(mesh, row_axes)
        if n_shards is None:
            n_shards = mesh_shards
        elif n_shards != mesh_shards:
            raise ValueError(
                f"n_shards={n_shards} conflicts with mesh shard count "
                f"{mesh_shards} over axes {row_axes}"
            )
    n_shards = max(int(n_shards or 1), 1)
    use_mesh = mesh is not None and mesh_shards > 1 and hash_name == "xash"

    n_values = len(corpus.unique_values)
    stats = BuildStats(
        n_shards=n_shards,
        mesh_shape=(
            {a: int(mesh.shape[a]) for a in row_axes} if use_mesh else None
        ),
        values_total=n_values,
        rows_total=corpus.total_rows,
        bytes_hashed=int(corpus.unique_enc.size),
        shard_values=np.diff(distributed.shard_bounds(n_values, n_shards))
        .astype(int).tolist(),
        corpus_seconds=corpus.build_seconds,
    )

    t0 = time.perf_counter()
    if use_mesh:
        value_lanes = ops.xash_values_mesh(
            corpus.unique_enc, cfg, mesh=mesh, row_axes=row_axes,
            times_out=stats.shard_hash_seconds,
        )
    else:
        value_lanes = np.zeros((n_values, cfg.lanes), dtype=np.uint32)
        vb = distributed.shard_bounds(n_values, n_shards)
        for i in range(n_shards):
            lo, hi = int(vb[i]), int(vb[i + 1])
            ts = time.perf_counter()
            value_lanes[lo:hi] = _hash_unique_values(
                corpus.unique_values[lo:hi], corpus.unique_enc[lo:hi], cfg,
                hash_name, corpus.avg_row_width(),
            )
            stats.shard_hash_seconds.append(time.perf_counter() - ts)
    stats.hash_seconds = time.perf_counter() - t0

    t0 = time.perf_counter()
    index = ShardedMateIndex._from_build(
        corpus, cfg, hash_name, value_lanes, n_shards, devices
    )
    stats.shard_rows = [s.n_rows for s in index.shards]
    stats.superkey_seconds = time.perf_counter() - t0  # superkeys + postings
    # per-shard column profiles (ranking subsystem): built where the tables
    # live and NEVER merged — the routed gate/score paths read each owning
    # shard's store, mirroring the resident-postings design above.
    t0 = time.perf_counter()
    for s in index.shards:
        s._profiles = profiles_lib.build_profiles(
            corpus, value_lanes, s.table_lo, s.table_hi, epoch=0
        )
    stats.profile_seconds = time.perf_counter() - t0
    stats.profile_bytes = sum(s._profiles.nbytes for s in index.shards)
    if use_mesh:
        index.attach_mesh(mesh, row_axes)
    stats.total_seconds = time.perf_counter() - t_start
    return index, stats
