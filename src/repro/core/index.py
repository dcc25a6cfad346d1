"""MATE inverted index with super keys (offline phase, paper §4/§5).

The index extends the classic single-attribute inverted index
``value -> [(table, col, row)]`` with one ``super key`` per row
(Eq. 4 → §5.1): the OR-aggregation of the row's per-cell hashes.

Hash functions are pluggable (``hash_name``): 'xash' uses the vectorised JAX
implementation; 'bf'/'ht'/'murmur'/'md5'/'city'/'simhash' are the paper's
baselines (computed per unique value, cached).  Per-unique-value hashing plus
an id-arena makes index build O(unique values) hash work instead of
O(total cells) — same trick the paper's artifact uses.

The offline phase itself is SHARDABLE (``build_index``): unique-value
hashing runs under ``shard_map`` over a device mesh
(``kernels.ops.xash_values_mesh``) while super-key aggregation and
posting-list construction run per contiguous row shard with a host-side
merge (``merge_shard_postings``) — every artifact (``value_lanes``,
``superkeys``, posting lists, CSR offsets) is BYTE-IDENTICAL to the
single-host ``MateIndex(...)`` constructor at any shard/device count.
``BuildStats`` records the per-phase accounting.

Index updates (§5.4): ``insert_table`` appends rows/postings/super keys;
``delete_table`` tombstones; ``update_cell`` re-hashes the affected row.
They operate on the merged dict/array state, so they compose identically
with sharded- and single-host-built indexes.

Columnar accessors for the batched online engine (``gather_candidates``,
``superkey_of_keys``, ``superkey_of_rows``) expose the index as contiguous
arrays — posting lists concatenated per candidate table in CSR layout and
query-key super keys hashed in one batched call — so the row filter can run
as a single kernel launch with no per-row dict lookups.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core import encoding, hashes, xash
from repro.core import profiles as profiles_lib
from repro.core.corpus import Corpus, Table

_XASH_CHUNK = 1 << 15


def _resolve_cfg(
    corpus: Corpus, cfg: xash.XashConfig, hash_name: str,
    use_corpus_char_freq: bool,
) -> xash.XashConfig:
    """Apply the corpus-level char-frequency prior (§5.2.1) when asked.

    replace() keeps every other knob (bits/width, ablation flags) of the
    caller's config intact.  Shared by the single-host constructor and the
    sharded builder so both resolve the SAME effective config.
    """
    if use_corpus_char_freq and hash_name == "xash":
        cfg = dataclasses.replace(
            cfg, char_freq=tuple(corpus.char_frequencies().tolist())
        )
    return cfg


def _hash_unique_values(
    values: list[str],
    enc: np.ndarray,
    cfg: xash.XashConfig,
    hash_name: str,
    avg_row_width: float,
) -> np.ndarray:
    """uint32[n_unique, lanes] hash lanes per unique value."""
    n = len(values)
    out = np.zeros((n, cfg.lanes), dtype=np.uint32)
    if hash_name == "xash":
        for s in range(0, n, _XASH_CHUNK):
            out[s : s + _XASH_CHUNK] = np.asarray(
                xash.xash(enc[s : s + _XASH_CHUNK], cfg)
            )
        return out
    if hash_name == "bf":
        n_hash = hashes.optimal_bloom_hashes(cfg.bits, avg_row_width)
        fn = hashes.make_bloom(n_hash)
    else:
        fn = hashes.BASELINE_HASHES[hash_name]
    shift_mask = (1 << 32) - 1
    for i, v in enumerate(values):
        h = fn(v, cfg.bits)
        for lane in range(cfg.lanes):
            out[i, lane] = (h >> (32 * lane)) & shift_mask
    return out


def _aggregate_superkeys(
    cell_value_ids: np.ndarray, value_lanes: np.ndarray, lanes: int
) -> np.ndarray:
    """OR per-cell hash lanes into per-row super keys (vectorised)."""
    n_rows = cell_value_ids.shape[0]
    sk = np.zeros((n_rows, lanes), dtype=np.uint32)
    valid = cell_value_ids >= 0
    safe_ids = np.where(valid, cell_value_ids, 0)
    gathered = value_lanes[safe_ids]  # [rows, cols, lanes]
    gathered[~valid] = 0
    np.bitwise_or.reduce(gathered, axis=1, out=sk)
    return sk


# ---------------------------------------------------------------------------
# Posting-list construction (sharded unit + host-side merge)
# ---------------------------------------------------------------------------


def _shard_postings(
    cell_value_ids: np.ndarray, row_lo: int, row_hi: int, n_values: int
) -> tuple[np.ndarray, np.ndarray]:
    """Posting-list items of rows ``[row_lo, row_hi)`` in mergeable form.

    Returns ``(payload, counts)``: ``payload`` int64[m, 2] of
    (global_row, col) grouped by ascending value id — row-major within a
    value id, the PL order the scalar engine fetches — and ``counts``
    int64[n_values] items per value id.  One call over the full row range is
    exactly the single-host build; per-shard calls merge via
    ``merge_shard_postings``.
    """
    ids = cell_value_ids[row_lo:row_hi]
    rows_idx, cols_idx = np.nonzero(ids >= 0)
    vids = ids[rows_idx, cols_idx]
    order = np.argsort(vids, kind="stable")
    payload = np.stack(
        [rows_idx[order] + row_lo, cols_idx[order]], axis=1
    ).astype(np.int64)
    counts = np.bincount(vids, minlength=n_values).astype(np.int64)
    return payload, counts


def _intern_value(index, value: str) -> int:
    """Resolve ``value`` in the corpus value arena, interning (and hashing)
    it if new — the shared §5.4 mutation primitive.  ``index`` is anything
    with ``corpus``/``cfg``/``hash_name``/``value_lanes`` (``MateIndex`` or
    ``routing.ShardedMateIndex``, whose value arena is replicated)."""
    corpus = index.corpus
    vid = corpus.value_of.get(value)
    if vid is not None:
        return vid
    vid = len(corpus.unique_values)
    corpus.value_of[value] = vid
    corpus.unique_values.append(value)
    new_enc = encoding.encode_values([value], corpus.max_len)
    corpus.unique_enc = np.concatenate([corpus.unique_enc, new_enc])
    index.value_lanes = np.concatenate(
        [
            index.value_lanes,
            _hash_unique_values(
                [value], new_enc, index.cfg, index.hash_name,
                corpus.avg_row_width(),
            ),
        ]
    )
    return vid


def _csr_ptr(counts: np.ndarray) -> np.ndarray:
    ptr = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=ptr[1:])
    return ptr


def merge_shard_postings(
    payloads: list[np.ndarray], counts: list[np.ndarray], n_values: int
) -> tuple[np.ndarray, np.ndarray]:
    """Merge per-shard posting payloads into the global CSR layout.

    Shards cover contiguous ascending row ranges, so placing each shard's
    per-vid group after the previous shards' groups reproduces the global
    row-major order within every value id — the merged ``(payload, ptr)`` is
    byte-identical to a single-host ``_shard_postings`` over all rows.
    """
    total = (
        np.sum(np.stack(counts), axis=0)
        if counts
        else np.zeros(n_values, dtype=np.int64)
    )
    ptr = _csr_ptr(total)
    payload = np.empty((int(ptr[-1]), 2), dtype=np.int64)
    write_at = ptr[:-1].copy()  # next free slot per value id
    for pl, cnt in zip(payloads, counts):
        if not len(pl):
            continue
        group_start = np.cumsum(cnt) - cnt  # this shard's per-vid offsets
        within = np.arange(len(pl), dtype=np.int64) - np.repeat(group_start, cnt)
        payload[np.repeat(write_at, cnt) + within] = pl
        write_at += cnt
    return payload, ptr


def _postings_dict(payload: np.ndarray, ptr: np.ndarray) -> dict[int, np.ndarray]:
    """Explode a CSR posting store into the per-value dict the index serves
    (entries are views into ``payload``; §5.4 mutations replace them with
    fresh arrays, never write through)."""
    postings: dict[int, np.ndarray] = {}
    for vid in range(len(ptr) - 1):
        lo, hi = int(ptr[vid]), int(ptr[vid + 1])
        if hi > lo:
            postings[vid] = payload[lo:hi]
    return postings


@dataclasses.dataclass
class BuildStats:
    """Offline-phase accounting for one ``build_index`` run.

    ``shard_values`` / ``shard_rows`` are the balanced contiguous partitions
    the build used (values for the hash pass, corpus rows for super keys and
    postings).  ``shard_hash_seconds`` is per-shard hash wall time: measured
    per shard on the host-sharded path; on the mesh path every launch is an
    SPMD collective, so each shard's entry is the per-launch total it
    participated in (lockstep by construction).
    """

    n_shards: int = 1
    mesh_shape: dict[str, int] | None = None  # None: no device mesh
    values_total: int = 0
    rows_total: int = 0
    bytes_hashed: int = 0  # encoded bytes fed to the unique-value hash pass
    shard_values: list[int] = dataclasses.field(default_factory=list)
    shard_rows: list[int] = dataclasses.field(default_factory=list)
    shard_hash_seconds: list[float] = dataclasses.field(default_factory=list)
    hash_seconds: float = 0.0
    superkey_seconds: float = 0.0
    postings_seconds: float = 0.0
    merge_seconds: float = 0.0
    profile_seconds: float = 0.0  # per-column ProfileStore pass (ranking)
    profile_bytes: int = 0  # ProfileStore footprint (all arrays)
    total_seconds: float = 0.0

    @property
    def sharded(self) -> bool:
        return self.n_shards > 1


@dataclasses.dataclass
class CandidateBlock:
    """All PL items for a set of query values, concatenated per candidate
    table (CSR layout) — the contiguous feed for one batched filter launch.

    Tables are ordered by descending item count (ties by ascending table id),
    the same order Algorithm 1 visits them, so rule-1 cutoffs apply to CSR
    prefixes.  Within a table, items keep fetch order (value-major, PL order).
    """

    rows: np.ndarray  # int64[N] global row ids, grouped by table
    value_idx: np.ndarray  # int32[N] index into the queried ``values`` list
    table_ids: np.ndarray  # int64[T] candidate table ids
    table_ptr: np.ndarray  # int64[T+1] CSR boundaries into rows/value_idx

    @property
    def n_items(self) -> int:
        return int(self.rows.shape[0])

    @property
    def n_tables(self) -> int:
        return int(self.table_ids.shape[0])

    def table_slice(self, t: int) -> slice:
        return slice(int(self.table_ptr[t]), int(self.table_ptr[t + 1]))


class MateIndex:
    """Inverted index + per-row super keys for one corpus."""

    def __init__(
        self,
        corpus: Corpus,
        cfg: xash.XashConfig = xash.DEFAULT_CONFIG,
        hash_name: str = "xash",
        use_corpus_char_freq: bool = False,
    ):
        cfg = _resolve_cfg(corpus, cfg, hash_name, use_corpus_char_freq)
        self.corpus = corpus
        self.cfg = cfg
        self.hash_name = hash_name

        self.value_lanes = _hash_unique_values(
            corpus.unique_values,
            corpus.unique_enc,
            cfg,
            hash_name,
            corpus.avg_row_width(),
        )
        self.superkeys = _aggregate_superkeys(
            corpus.cell_value_ids, self.value_lanes, cfg.lanes
        )

        # posting lists: value id -> int64[n, 2] (global_row, col); one
        # full-range shard of the same construction the sharded build merges
        n_values = len(corpus.unique_values)
        payload, counts = _shard_postings(
            corpus.cell_value_ids, 0, corpus.total_rows, n_values
        )
        self.postings = _postings_dict(payload, _csr_ptr(counts))
        self._deleted_tables: set[int] = set()
        self._mutations = 0
        self._device_store = None
        self._device_store_epoch = -1
        self._deleted_mask: np.ndarray | None = None
        self._deleted_mask_epoch = -1
        self._profiles: profiles_lib.ProfileStore | None = None

    @classmethod
    def _from_build(
        cls,
        corpus: Corpus,
        cfg: xash.XashConfig,
        hash_name: str,
        value_lanes: np.ndarray,
        superkeys: np.ndarray,
        payload: np.ndarray,
        ptr: np.ndarray,
    ) -> "MateIndex":
        """Assemble an index from prebuilt (possibly shard-merged) artifacts
        — the ``build_index`` seam.  ``cfg`` must already be resolved."""
        self = cls.__new__(cls)
        self.corpus = corpus
        self.cfg = cfg
        self.hash_name = hash_name
        self.value_lanes = value_lanes
        self.superkeys = superkeys
        self.postings = _postings_dict(payload, ptr)
        self._deleted_tables = set()
        self._mutations = 0
        self._device_store = None
        self._device_store_epoch = -1
        self._deleted_mask = None
        self._deleted_mask_epoch = -1
        self._profiles = None
        return self

    @property
    def bits(self) -> int:
        """Hash width this index was built at (128/256/512 → 4/8/16 lanes)."""
        return self.cfg.bits

    @property
    def mutation_epoch(self) -> int:
        """Monotonic count of §5.4 mutations (insert/delete/update) applied
        to this index.  Anything derived from index state at epoch e —
        cached top-k results, cached candidate counts — is valid exactly
        while ``mutation_epoch == e`` still holds (``serve.cache`` keys its
        invalidation on this)."""
        return self._mutations

    def device_store(self):
        """Device-resident superkey store (``kernels.ops.DeviceStore``, the
        gather kernel's packed layout).

        The gather-fused filter backend DMA-gathers candidate rows from this
        store inside the kernel, so it must track every §5.4 mutation:
        the upload is re-done (lazily, on next access) whenever
        ``mutation_epoch`` moved past the epoch the resident copy was taken
        at — in-place superkey edits (``delete_table`` zeroing,
        ``update_cell`` re-hash) bump the epoch too, so a stale device copy
        can never be served.
        """
        if self._device_store is None or self._device_store_epoch != self._mutations:
            from repro.kernels import ops

            self._device_store = ops.device_store(self.superkeys)
            self._device_store_epoch = self._mutations
        return self._device_store

    # -- column profiles (ranking subsystem) ----------------------------------

    def profiles(self) -> profiles_lib.ProfileStore:
        """Per-column ``ProfileStore`` for this index, epoch-pinned like the
        device superkey store: ``build_index`` populates it at build time,
        and any §5.4 mutation invalidates it — the next access rebuilds from
        the mutated corpus arenas (lazily, exactly the ``device_store``
        refresh discipline), so the profile gate can never prune against a
        value set the lake no longer has."""
        if self._profiles is None or self._profiles.epoch != self._mutations:
            self._profiles = profiles_lib.build_profiles(
                self.corpus, self.value_lanes, epoch=self._mutations
            )
        return self._profiles

    def gate_candidates(
        self, distinct_keys: list[tuple[str, ...]], table_ids: np.ndarray
    ) -> np.ndarray:
        """Profile gate: bool[n] keep-mask over candidate table ids.

        False only for tables whose profiles PROVE joinability 0 against
        every distinct query key (``profiles.gate_tables``) — pure pruning,
        the verified top-k set is unchanged."""
        kvi, probe, len_bucket, vclass = profiles_lib.query_gate_inputs(
            distinct_keys, self.hash_values
        )
        return profiles_lib.gate_tables(
            self.profiles(),
            np.asarray(table_ids, dtype=np.int64),
            kvi, probe, len_bucket, vclass, len(distinct_keys[0]),
        )

    def profile_features(
        self, table_ids: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Scoring-head feature gather: (card_max, n_rows, sketch) rows for
        the given table ids (``core.ranking.quality_scores`` input)."""
        store = self.profiles()
        ids = np.asarray(table_ids, dtype=np.int64)
        return store.card_max[ids], store.n_rows[ids], store.sketch[ids]

    # -- online-side hashing --------------------------------------------------

    def hash_values(self, values: list[str]) -> np.ndarray:
        """Hash arbitrary (query-side) strings with this index's hash fn."""
        enc = encoding.encode_values(values, self.cfg.max_len)
        return _hash_unique_values(
            values, enc, self.cfg, self.hash_name, self.corpus.avg_row_width()
        )

    def superkey_of_keys(self, keys: list[tuple[str, ...]]) -> np.ndarray:
        """Batched query-side key hashing: uint32[len(keys), lanes].

        The super key of a query key is the OR of its value hashes (Alg. 1
        line 6).  For XASH the whole key set is encoded as one
        ``[n_keys, |Q|, max_len]`` block and hashed by a single
        ``xash.superkey`` call; baseline hashes fall back to per-unique-value
        hashing + OR.  Bit-identical to hashing each value separately.

        Every key must have the same width (one n-ary query per batch):
        ragged widths raise ``ValueError`` on BOTH hash paths — the xash
        branch would otherwise crash (or worse, mis-reshape) in the batched
        encode, and the baseline OR loop would silently hash a different
        query than the caller asked for.
        """
        lanes = self.cfg.lanes
        if not keys:
            return np.zeros((0, lanes), dtype=np.uint32)
        width = len(keys[0])
        for i, key in enumerate(keys):
            if len(key) != width:
                raise ValueError(
                    f"ragged key widths: key 0 has {width} value(s) but key"
                    f" {i} has {len(key)} — superkey_of_keys hashes one"
                    " fixed-width n-ary query key set per call"
                )
        if self.hash_name == "xash":
            flat = [v for key in keys for v in key]
            enc = encoding.encode_values(flat, self.cfg.max_len)
            enc = enc.reshape(len(keys), width, self.cfg.max_len)
            return np.asarray(xash.superkey(enc, self.cfg))
        flat_values = sorted({v for key in keys for v in key})
        value_lanes = self.hash_values(flat_values)
        lane_of = {v: value_lanes[i] for i, v in enumerate(flat_values)}
        out = np.zeros((len(keys), lanes), dtype=np.uint32)
        for i, key in enumerate(keys):
            for v in key:
                out[i] |= lane_of[v]
        return out

    # -- lookups --------------------------------------------------------------

    def _deleted_row_mask(self) -> np.ndarray:
        """bool[total_rows] — True for rows of tombstoned tables.

        Cached on ``mutation_epoch``: ``fetch_postings`` runs once per value
        per query, and rebuilding ``list(self._deleted_tables)`` + ``np.isin``
        there made a delete-heavy lake pay O(values × deleted) on every
        gather.  The mask costs one O(total_rows) pass per mutation epoch
        and turns each fetch's tombstone filter into a direct index.
        """
        if self._deleted_mask_epoch != self._mutations:
            mask = np.zeros(self.corpus.total_rows, dtype=bool)
            rb = self.corpus.row_base
            for t in self._deleted_tables:
                mask[int(rb[t]) : int(rb[t + 1])] = True
            self._deleted_mask = mask
            self._deleted_mask_epoch = self._mutations
        return self._deleted_mask

    def fetch_postings(self, value: str) -> np.ndarray:
        """PL items for a value: int64[n, 2] of (global_row, col)."""
        vid = self.corpus.value_of.get(value)
        if vid is None or vid not in self.postings:
            return np.zeros((0, 2), dtype=np.int64)
        pl = self.postings[vid]
        if self._deleted_tables:
            pl = pl[~self._deleted_row_mask()[pl[:, 0]]]
        return pl

    def superkey_of_rows(self, global_rows: np.ndarray) -> np.ndarray:
        """Block gather of per-row super keys: uint32[len(global_rows), lanes]."""
        return self.superkeys[np.asarray(global_rows, dtype=np.int64)]

    def gather_candidates(self, values: list[str]) -> CandidateBlock:
        """Concatenate the posting lists of ``values`` into one CSR block.

        One fetch per value, then a single vectorised group-by-table pass —
        the per-(row, value) dict bookkeeping of the scalar engine collapses
        into three contiguous arrays the filter kernel can consume directly.
        """
        parts_rows: list[np.ndarray] = []
        parts_vidx: list[np.ndarray] = []
        for i, value in enumerate(values):
            pl = self.fetch_postings(value)
            if len(pl):
                parts_rows.append(pl[:, 0])
                parts_vidx.append(np.full(len(pl), i, dtype=np.int32))
        if not parts_rows:
            return CandidateBlock(
                rows=np.zeros(0, dtype=np.int64),
                value_idx=np.zeros(0, dtype=np.int32),
                table_ids=np.zeros(0, dtype=np.int64),
                table_ptr=np.zeros(1, dtype=np.int64),
            )
        rows = np.concatenate(parts_rows)
        vidx = np.concatenate(parts_vidx)
        tids = np.asarray(self.corpus.table_of_row(rows), dtype=np.int64)
        uniq, inv, counts = np.unique(tids, return_inverse=True, return_counts=True)
        # Algorithm 1 visit order: descending item count, ties by table id.
        order = np.lexsort((uniq, -counts))
        rank = np.empty(len(uniq), dtype=np.int64)
        rank[order] = np.arange(len(uniq))
        perm = np.argsort(rank[inv], kind="stable")
        counts_sorted = counts[order]
        ptr = np.zeros(len(uniq) + 1, dtype=np.int64)
        np.cumsum(counts_sorted, out=ptr[1:])
        return CandidateBlock(
            rows=rows[perm],
            value_idx=vidx[perm],
            table_ids=uniq[order],
            table_ptr=ptr,
        )

    # -- index updates (§5.4) ---------------------------------------------------

    def insert_table(self, cells: list[list[str]], name: str = "") -> int:
        """Append a new table; returns its table id."""
        self._mutations += 1
        corpus = self.corpus
        table = Table(table_id=len(corpus.tables), cells=cells, name=name)
        n_rows, n_cols = table.n_rows, table.n_cols
        if n_cols > corpus.max_cols:
            pad = n_cols - corpus.max_cols
            corpus.cell_value_ids = np.pad(
                corpus.cell_value_ids, ((0, 0), (0, pad)), constant_values=-1
            )
            corpus.max_cols = n_cols
        corpus.tables.append(table)
        corpus.row_base = np.append(corpus.row_base, corpus.row_base[-1] + n_rows)
        corpus.n_cols = np.append(corpus.n_cols, n_cols)
        base = corpus.total_rows
        corpus.total_rows += n_rows

        new_ids = np.full((n_rows, corpus.max_cols), -1, dtype=np.int32)
        new_value_strs: list[str] = []
        for r, row in enumerate(cells):
            for c, v in enumerate(row):
                vid = corpus.value_of.get(v)
                if vid is None:
                    vid = len(corpus.unique_values)
                    corpus.value_of[v] = vid
                    corpus.unique_values.append(v)
                    new_value_strs.append(v)
                new_ids[r, c] = vid
        if new_value_strs:
            new_enc = encoding.encode_values(new_value_strs, corpus.max_len)
            corpus.unique_enc = np.concatenate([corpus.unique_enc, new_enc])
            new_lanes = _hash_unique_values(
                new_value_strs, new_enc, self.cfg, self.hash_name,
                corpus.avg_row_width(),
            )
            self.value_lanes = np.concatenate([self.value_lanes, new_lanes])
        corpus.cell_value_ids = np.concatenate([corpus.cell_value_ids, new_ids])
        new_sk = _aggregate_superkeys(new_ids, self.value_lanes, self.cfg.lanes)
        self.superkeys = np.concatenate([self.superkeys, new_sk])
        for r in range(n_rows):
            for c in range(len(cells[r])):
                vid = new_ids[r, c]
                item = np.array([[base + r, c]], dtype=np.int64)
                self.postings[vid] = (
                    np.concatenate([self.postings[vid], item])
                    if vid in self.postings
                    else item
                )
        return table.table_id

    def delete_table(self, table_id: int) -> None:
        """Tombstone a table (PL items filtered at fetch; §5.4 delete)."""
        self._mutations += 1
        self._deleted_tables.add(table_id)
        lo, hi = self.corpus.row_base[table_id], self.corpus.row_base[table_id + 1]
        self.superkeys[lo:hi] = 0

    def update_cell(self, table_id: int, row: int, col: int, value: str) -> None:
        """Update one cell: re-hash the affected row's super key (§5.4)."""
        self._mutations += 1
        corpus = self.corpus
        grow = int(corpus.row_base[table_id]) + row
        old_vid = int(corpus.cell_value_ids[grow, col])
        vid = _intern_value(self, value)
        corpus.tables[table_id].cells[row][col] = value
        corpus.cell_value_ids[grow, col] = vid
        # postings: drop old item, add new
        if old_vid in self.postings:
            pl = self.postings[old_vid]
            keep = ~((pl[:, 0] == grow) & (pl[:, 1] == col))
            self.postings[old_vid] = pl[keep]
        item = np.array([[grow, col]], dtype=np.int64)
        self.postings[vid] = (
            np.concatenate([self.postings[vid], item]) if vid in self.postings else item
        )
        # full re-hash of the row's super key
        self.superkeys[grow] = _aggregate_superkeys(
            corpus.cell_value_ids[grow : grow + 1], self.value_lanes, self.cfg.lanes
        )[0]


def index_artifacts_equal(a: "MateIndex", b: "MateIndex") -> bool:
    """True iff every offline artifact is byte-identical: value hash lanes
    (incl. dtype), per-row super keys, and per-value posting lists.

    The sharded-build contract's single definition — shared by the
    ``index_build`` bench gate, the launch dry-run and the equivalence test
    matrix, so the three can't drift apart on what "identical" means.
    """
    return (
        a.value_lanes.dtype == b.value_lanes.dtype
        and np.array_equal(a.value_lanes, b.value_lanes)
        and np.array_equal(a.superkeys, b.superkeys)
        and set(a.postings) == set(b.postings)
        and all(
            a.postings[v].dtype == b.postings[v].dtype
            and np.array_equal(a.postings[v], b.postings[v])
            for v in b.postings
        )
    )


# ---------------------------------------------------------------------------
# Sharded offline build (the distributed counterpart of ``MateIndex(...)``)
# ---------------------------------------------------------------------------


def build_index(
    corpus: Corpus,
    cfg: xash.XashConfig = xash.DEFAULT_CONFIG,
    hash_name: str = "xash",
    use_corpus_char_freq: bool = False,
    *,
    mesh=None,
    row_axes: tuple[str, ...] | None = None,
    n_shards: int | None = None,
) -> tuple["MateIndex", BuildStats]:
    """Offline phase (§4/§5) with every pass sharded, plus build accounting.

    With a ``mesh`` of >1 devices, unique-value XASH hashing runs under
    ``shard_map`` over ``row_axes`` (``kernels.ops.xash_values_mesh``) —
    the throughput-critical pass, the same way ``core.distributed`` shards
    the online filter.  Super-key aggregation and posting-list construction
    run per contiguous row shard on the host and merge deterministically
    (``merge_shard_postings``).  Without a mesh, ``n_shards`` splits the
    same passes host-side (shard-merge machinery without devices); the
    default ``n_shards=1`` IS the single-host path.

    Every path yields artifacts byte-identical to ``MateIndex(corpus, ...)``:
    per-value hashing has no cross-value term, super keys are per-row, and
    the posting merge preserves global row-major order within each value id.
    Baseline hashes (``hash_name != 'xash'``) are host-side Python and fall
    back to host-sharded hashing under any mesh.

    Returns ``(index, BuildStats)``.
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
    # one device (or one shard) falls back to the single-host pass; baseline
    # hashes are host-side Python functions, so only xash hashes on device
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
    )
    avg_w = corpus.avg_row_width()

    # -- unique-value hashing (the throughput-critical pass) ----------------
    t0 = time.perf_counter()
    if use_mesh:
        from repro.kernels import ops

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
                hash_name, avg_w,
            )
            stats.shard_hash_seconds.append(time.perf_counter() - ts)
    stats.hash_seconds = time.perf_counter() - t0

    # -- per-row-shard super keys + posting lists ---------------------------
    rb = distributed.shard_bounds(corpus.total_rows, n_shards)
    stats.shard_rows = np.diff(rb).astype(int).tolist()
    t0 = time.perf_counter()
    sk_parts = [
        _aggregate_superkeys(
            corpus.cell_value_ids[int(rb[i]) : int(rb[i + 1])],
            value_lanes, cfg.lanes,
        )
        for i in range(n_shards)
    ]
    stats.superkey_seconds = time.perf_counter() - t0
    t0 = time.perf_counter()
    parts = [
        _shard_postings(corpus.cell_value_ids, int(rb[i]), int(rb[i + 1]), n_values)
        for i in range(n_shards)
    ]
    stats.postings_seconds = time.perf_counter() - t0

    # -- host-side merge ----------------------------------------------------
    t0 = time.perf_counter()
    superkeys = np.concatenate(sk_parts)
    payload, ptr = merge_shard_postings(
        [p for p, _ in parts], [c for _, c in parts], n_values
    )
    index = MateIndex._from_build(
        corpus, cfg, hash_name, value_lanes, superkeys, payload, ptr
    )
    stats.merge_seconds = time.perf_counter() - t0

    # -- per-column profiles (ranking subsystem) ----------------------------
    # Sharded over contiguous TABLE ranges (profiles are per-table, so the
    # row bounds above don't apply) and concatenated — byte-identical to the
    # single-host pass at any shard count, like every artifact above.
    t0 = time.perf_counter()
    n_tables = len(corpus.row_base) - 1
    tb = distributed.shard_bounds(n_tables, n_shards)
    index._profiles = profiles_lib.merge_profiles(
        [
            profiles_lib.build_profiles(
                corpus, value_lanes, int(tb[i]), int(tb[i + 1])
            )
            for i in range(n_shards)
        ]
    )
    stats.profile_seconds = time.perf_counter() - t0
    stats.profile_bytes = index._profiles.nbytes

    stats.total_seconds = time.perf_counter() - t_start
    return index, stats
