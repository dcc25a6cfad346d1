"""Batched kernel-backed discovery engine — the beyond-paper fast path.

The faithful Algorithm 1 (discovery.py) is a branchy per-row scan: ideal on a
CPU, hostile to a vector unit.  This engine restructures the online phase
into three steps — plan, one filter launch, score — each over contiguous
blocks:

  * **plan** (``plan_query``): query-side key hashing is ONE batched
    ``xash.superkey`` call (``MateIndex.superkey_of_keys``), not per-value
    host hashing; candidate posting lists are gathered into a CSR block per
    query (``MateIndex.gather_candidates``): rows, value indices and table
    boundaries as three contiguous arrays — no per-row dict lookups;
    value/key eligibility is two id vectors (each item's and each key's init
    value) that the launch compares tile by tile;
  * **launch** (``plan_and_count``): every request of a group shares ONE
    §6.3 subsumption launch through ``kernels.ops.filter_hits_table_counts``,
    which also reduces the match matrix to per-table eligible-hit counts
    (a row reduction + segment-sum over the CSR table ids).  On the FUSED
    backends (``'fused'``, and ``'fused-gather'`` — the TPU platform
    default; see ``kernels.registry`` for the one precedence rule) the
    reduction happens INSIDE the filter kernel, so the match matrix never
    exists even in HBM and only the counts vector comes back;
    ``'fused-gather'`` additionally DMA-gathers each candidate row block
    from the device-resident superkey store (``MateIndex.device_store()``,
    refreshed on §5.4 mutation epochs), so the host never gathers the
    candidate superkeys either (``DiscoveryStats.gather_bytes_saved``).  It
    demotes to 'fused' when the store is over the device budget, counted in
    ``DiscoveryStats.gather_demotions``; launches over the scatter tile's
    table cap split into table chunks (``kernels.ops.table_chunks``).  The
    other backends return the match matrix to the host in one transfer;
  * **score** (``score_from_counts``): tables are visited in the same
    descending posting-list order as Algorithm 1; rule 1 (the global
    cutoff: the first table whose posting list is at/below the bound prunes
    the whole suffix) and a *stronger* rule 2 apply — the exact
    filtered-candidate count per table replaces the paper's incremental
    ``L_t - r_checked + r_match`` bound, so strictly more tables are skipped
    before verification.  Rule 2 applies before the heap fills too: the
    bound is then 0, so a table with no filter-surviving pair (joinability
    0, which the heap would discard) is skipped without a re-gather;
  * only filter-surviving pairs are verified on the host: the faithful
    engine's exact ``calculateJ``, computed on lake value ids in numpy
    rather than one string comparison per pair.  Where the launch kept no
    match matrix, a surviving table re-tests just its own eligible pairs.

Hash width is a first-class knob: every array here is ``lanes``-wide
(``XashConfig(bits=...)`` → 4/8/16 uint32 lanes for 128/256/512 bits), so the
same engine and kernels serve any width the index was built at — the paper's
Table 1/2 FP-rate vs filter-bandwidth tradeoff (see
``benchmarks/bench_fp_rate.py``).

``discover_many`` is the composition for a list of requests;
``session.MateSession.discover`` and ``serve.engine.DiscoveryEngine`` call
the two phases themselves, the latter so its caches can sit between them.

Top-k results are BIT-IDENTICAL to Algorithm 1 (ids, joinability scores and
mappings): both engines visit tables in the same order with the same
replace-only-if-strictly-greater heap, and every pruned table provably cannot
enter the heap (its joinability is bounded by the pruning threshold, which is
0 — a joinability the heap discards — until the heap is full).
"""

from __future__ import annotations

import dataclasses
import heapq
import time

import numpy as np

from repro import telemetry
from repro.core import discovery as seq
from repro.core import ranking
from repro.core.corpus import Table
from repro.core.discovery import DiscoveryStats, TopKEntry
from repro.core.index import CandidateBlock, MateIndex
from repro.kernels import ops, registry
from repro.kernels.registry import Backend


@dataclasses.dataclass
class QueryPlan:
    """Precomputed per-query state feeding the batched filter."""

    query: Table
    q_cols: list[int]
    distinct_keys: list[tuple]
    key_ids: np.ndarray  # int32[K, width] lake value ids of each key (-1:
    # a value the lake lacks), what exact verification compares
    q_sk: np.ndarray  # uint32[K, lanes] batched query-key super keys
    block: CandidateBlock  # CSR candidate rows grouped per table
    # init-value eligibility in id form: item i and key k are eligible
    # exactly when the item's posting value is the key's init value
    elig: ops.Eligibility
    stats: DiscoveryStats
    # the same eligibility by init value: key ids grouped by their init
    # value, and each value's offsets into them (``_eligible_pairs``)
    value_keys: np.ndarray  # int64[K]
    value_key_ptr: np.ndarray  # int64[n_values + 1]


def _gate_block(block: CandidateBlock, keep: np.ndarray) -> CandidateBlock:
    """Drop gated tables (and their items) from a CSR candidate block.

    ``keep`` is the profile gate's per-table mask; the surviving tables
    stay in PL-descending order (a subsequence of a sorted sequence), so
    the rule-1 prefix-cutoff argument downstream is unchanged."""
    lengths = np.diff(block.table_ptr)
    item_keep = np.repeat(keep, lengths)
    kept_lengths = lengths[keep]
    ptr = np.zeros(kept_lengths.shape[0] + 1, dtype=np.int64)
    np.cumsum(kept_lengths, out=ptr[1:])
    return CandidateBlock(
        rows=block.rows[item_keep],
        value_idx=block.value_idx[item_keep],
        table_ids=block.table_ids[keep],
        table_ptr=ptr,
    )


def plan_query(
    index: MateIndex, query: Table, q_cols: list[int],
    init_mode: str = "cardinality",
    *,
    profile_gate: bool = False,
    rid: int | None = None,
) -> QueryPlan:
    """Initialization phase (§6.1) in columnar form: one hash launch, one
    posting-list gather, and each key's init value.

    ``profile_gate=True`` drops candidate tables whose column profiles
    PROVE joinability 0 (``MateIndex.gate_candidates`` — presence-mask /
    length-bucket / char-class / column-count necessary conditions) before
    any superkey is gathered or filtered: pure pruning, the verified top-k
    set is unchanged; ``stats.tables_gated`` / ``gate_bytes_saved`` count
    the work the filter launches never saw.  ``tables_fetched`` /
    ``pl_items_total`` stay PRE-gate (what the posting lists produced).
    ``rid`` labels the ``plan.query`` span (the serving request's id)."""
    with telemetry.span("plan.query", rid=rid):
        stats = DiscoveryStats()
        with telemetry.span("plan.init_column"):
            init_col = seq.init_column_selection(query, q_cols, init_mode, index)
            init_idx = q_cols.index(init_col)
        with telemetry.span("plan.hash_keys"):
            keys = [tuple(row[c] for c in q_cols) for row in query.cells]
            distinct_keys = list(dict.fromkeys(keys))
            q_sk = index.superkey_of_keys(distinct_keys)
        with telemetry.span("plan.key_ids"):
            value_of = index.corpus.value_of
            key_ids = np.array(
                [[value_of.get(v, -1) for v in key] for key in distinct_keys],
                dtype=np.int32,
            ).reshape(len(distinct_keys), len(q_cols))

        with telemetry.span("plan.gather_candidates"):
            values = list(dict.fromkeys(query.column(init_col)))
            block = index.gather_candidates(values)
        stats.pl_items_total = block.n_items
        stats.tables_fetched = block.n_tables
        if profile_gate and block.n_tables and distinct_keys:
            with telemetry.span("plan.profile_gate"):
                keep = index.gate_candidates(distinct_keys, block.table_ids)
                if not keep.all():
                    stats.tables_gated = int((~keep).sum())
                    n_before = block.n_items
                    block = _gate_block(block, keep)
                    # superkey lanes the filter launches now never gather/compare
                    stats.gate_bytes_saved = (
                        (n_before - block.n_items) * q_sk.shape[1] * 4
                    )
        with telemetry.span("plan.eligibility"):
            value_id = {v: i for i, v in enumerate(values)}
            # key kid is probed against items of value v only if the key's
            # init-column entry IS v (Alg. 1 matches per posting list)
            init_value = np.array(
                [value_id[key[init_idx]] for key in distinct_keys], dtype=np.int32
            )
            elig = ops.Eligibility(block.value_idx, init_value)
            value_keys = np.argsort(init_value, kind="stable")
            value_key_ptr = np.zeros(len(values) + 1, dtype=np.int64)
            np.cumsum(
                np.bincount(init_value, minlength=len(values)), out=value_key_ptr[1:]
            )
        telemetry.count("items", block.n_items)
        telemetry.count("tables", block.n_tables)
    return QueryPlan(
        query, q_cols, distinct_keys, key_ids, q_sk, block, elig, stats,
        value_keys, value_key_ptr,
    )


def _segment_ids(table_ptr: np.ndarray) -> np.ndarray:
    """int32 per-item table index of a CSR block."""
    return np.repeat(
        np.arange(table_ptr.shape[0] - 1, dtype=np.int32), np.diff(table_ptr)
    )


def _hits_counts_host(row_sk, q_sk, elig, seg, n_tables, backend: Backend):
    """Host-side hits + per-table counts: one filter launch, full readback.

    The non-fused backends' launch: every request starts with an empty heap
    (entry bound 0), so most hit blocks are about to be verified anyway and
    fusing the count reduction into the launch would add device work
    without saving a byte.
    """
    if not backend.device:
        return ops.filter_hits_table_counts(
            row_sk, q_sk, elig, seg, n_tables, backend="numpy"
        )
    hits = ops.filter_match_auto(row_sk, q_sk, backend=backend) & elig.dense()
    counts = np.bincount(
        seg, weights=hits.sum(axis=1), minlength=max(n_tables, 1)
    ).astype(np.int32)
    return hits, counts[:n_tables]


def eligible_count(plan: QueryPlan, value_idx: np.ndarray) -> int:
    """Eligible (item, key) pairs of the items with posting values
    ``value_idx``: each item counts the keys of its init value (the CSR)."""
    return int(np.diff(plan.value_key_ptr)[value_idx].sum())


def _eligible_pairs(plan: QueryPlan, value_idx: np.ndarray):
    """(item, key) index pairs of a slice's eligible probes — each item with
    every key whose init value is the item's posting value — in the order
    ``np.nonzero`` of the slice's ``plan.elig.dense()`` rows gives, without
    forming the dense [items, K] block."""
    start = plan.value_key_ptr[value_idx]
    n = plan.value_key_ptr[value_idx + 1] - start
    item = np.repeat(np.arange(value_idx.size), n)
    first = np.cumsum(n) - n
    key = plan.value_keys[np.repeat(start - first, n) + np.arange(item.size)]
    return item, key


@dataclasses.dataclass
class _Pairs:
    """A slice's filter-surviving (row, key) pairs, row-major: what
    ``np.nonzero`` of its hit mask gives (``np.nonzero`` calls
    ``nonzero``), without the [rows, K] mask."""

    rows: np.ndarray
    keys: np.ndarray

    def nonzero(self) -> tuple[np.ndarray, np.ndarray]:
        return self.rows, self.keys


def _verify_pairs(
    index: MateIndex, plan: QueryPlan, rows: np.ndarray, rs: np.ndarray, ks: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Exact verification of (row ``rows[rs[p]]``, key ``ks[p]``) pairs on
    lake value ids: each row of ``corpus.cell_value_ids`` is compared with
    the key's ids (``plan.key_ids``, -1 matching nothing) in numpy.  The
    injective mappings are grown one key value at a time: every column
    holding the value and not used by the mapping so far extends it.
    Returns bool[n] (the pair holds its key, as ``seq._verify_pair`` on
    the strings finds), and the packed code and key of every mapping
    found, with the code's base."""
    cells = index.corpus.cell_value_ids[rows[rs]]  # int32[n, max_cols]
    kv = plan.key_ids[ks]  # int32[n, width]
    pair = np.arange(rs.size)
    cols = np.zeros((rs.size, 0), dtype=np.int64)
    for i in range(kv.shape[1]):
        v = kv[pair, i, None]
        at = (cells[pair] == v) & (v >= 0)
        at[np.arange(pair.size)[:, None], cols] = False
        m, c = np.nonzero(at)
        pair, cols = pair[m], np.concatenate([cols[m], c[:, None]], axis=1)
    tp = np.zeros(rs.size, dtype=bool)
    tp[pair] = True
    base = max(cells.shape[1], 1)
    return tp, _pack(cols, base), ks[pair], base


def _calculate_j(
    index: MateIndex,
    plan: QueryPlan,
    rows: np.ndarray,
    hits: np.ndarray,
) -> tuple[int, tuple[int, ...] | None]:
    """Exact verification (Alg. 1 line 21) over filter-surviving pairs
    (``hits``: a slice's bool mask, or its ``_Pairs``), on lake value ids
    (``_verify_pairs``).  Counts, joinability and mapping (ties to the
    largest) are those of ``seq._verify_pair`` on the strings."""
    stats = plan.stats
    rs, ks = np.nonzero(hits)
    if not rs.size:
        return 0, None
    tp, codes, owners, base = _verify_pairs(index, plan, rows, rs, ks)
    n_tp = int(tp.sum())
    stats.verified_tp += n_tp
    stats.verified_fp += int(rs.size) - n_tp
    if not n_tp:
        return 0, None
    # distinct keys per mapping; the largest count wins, ties the largest
    # mapping (packed codes order as mapping tuples do)
    n_keys = max(len(plan.distinct_keys), 1)
    pairs = np.unique(codes * n_keys + owners)
    code, size = np.unique(pairs // n_keys, return_counts=True)
    best = np.lexsort((code, size))[-1]
    return int(size[best]), _unpack(int(code[best]), plan.key_ids.shape[1], base)


def _pack(pos: np.ndarray, base: int) -> np.ndarray:
    """int64 code of each mapping row, first column most significant."""
    code = np.zeros(pos.shape[0], dtype=np.int64)
    for i in range(pos.shape[1]):
        code = code * base + pos[:, i]
    return code


def _unpack(code: int, width: int, base: int) -> tuple[int, ...]:
    out = []
    for _ in range(width):
        code, c = divmod(code, base)
        out.append(c)
    return tuple(reversed(out))


class _TopK:
    """Algorithm 1's heap: push while filling, replace only if strictly
    greater — the tie semantics both engines share (bit-identical results)."""

    def __init__(self, k: int):
        self.k = k
        self.heap: list[tuple[int, int]] = []  # (J, -table_id) min-heap
        self.mapping: dict[int, tuple[int, ...] | None] = {}

    def bound(self) -> int:
        return self.heap[0][0] if len(self.heap) >= self.k else 0

    @property
    def full(self) -> bool:
        return len(self.heap) >= self.k

    def offer(self, tid: int, joinability: int, mapping) -> None:
        self.mapping[tid] = mapping
        if joinability <= 0:
            return
        if len(self.heap) < self.k:
            heapq.heappush(self.heap, (joinability, -tid))
        elif joinability > self.heap[0][0]:
            heapq.heapreplace(self.heap, (joinability, -tid))

    def entries(self) -> list[TopKEntry]:
        out = [
            TopKEntry(table_id=-neg, joinability=j, mapping=self.mapping.get(-neg))
            for j, neg in self.heap
        ]
        out.sort(key=lambda e: (-e.joinability, e.table_id))
        return out


def _ranked_entries(
    topk: _TopK, rank: str, scores: dict[int, float]
) -> list[TopKEntry]:
    """Order the heap's entries for the requested rank mode.

    ``rank='count'`` is the historical (-joinability, table_id) order;
    ``rank='quality'`` annotates each entry with its scoring-head value and
    sorts (-quality, -joinability, table_id).  Either way the entries come
    from the SAME heap — rank never changes set membership."""
    entries = topk.entries()
    if rank != "quality":
        return entries
    entries = [
        dataclasses.replace(e, quality=float(scores.get(e.table_id, 0.0)))
        for e in entries
    ]
    entries.sort(key=lambda e: (-e.quality, -e.joinability, e.table_id))
    return entries


def _score_tables(
    index: MateIndex,
    plan: QueryPlan,
    topk: _TopK,
    hits: np.ndarray | None,
    counts: np.ndarray,
    row_sk: np.ndarray | None = None,
) -> None:
    """Verify (or rule-1/rule-2-prune) every table of the plan's block.

    ``counts`` is the launch's per-table eligible-hit counts; ``hits`` is
    the plan's host slice of the match matrix, or None — the FUSED
    counts-only launch, where the match matrix was never produced at all
    (and every bound-cache replay).  Surviving tables' hits are then
    recomputed on demand from ``row_sk``, testing only the table's eligible
    (row, key) pairs (``_eligible_pairs``: the same pairs as ``plan.elig``,
    the same subsumption predicate → bit-identical verification inputs;
    ``regather_pairs`` counts the pairs tested); pruned tables cost nothing
    beyond their 4 count bytes.  On the GATHER-fused and routed paths even
    ``row_sk`` is None — the host never gathered the candidate superkeys —
    and surviving tables gather just their own slice from the index store
    (the same ``superkeys`` array every other path reads: bit-identical).

    Rule 1: tables are PL-desc sorted, so the first whose posting list is
    at/below the bound of a full heap prunes the whole suffix.  Rule 2
    prunes a table whenever its count is at or below ``topk.bound()``, full
    heap or not: before the heap fills the bound is 0, so exactly the tables
    with no filter-surviving pair are skipped (counted in
    ``tables_pruned_empty`` as well as ``tables_pruned_rule2``).  The filter
    has no false negatives, so joinability ≤ count, and the heap discards
    joinability 0 — the top-k and the verified pairs are unchanged.
    """
    block, stats = plan.block, plan.stats
    ptr, rows, n_tables = block.table_ptr, block.rows, block.n_tables
    lazy = hits is None
    # per-table clocks only while tracing: the slice re-gather and the exact
    # verification, summed into the score.tables span
    timed = telemetry.enabled()
    clock = time.perf_counter_ns
    regather_ns = exact_ns = 0
    if timed:
        before = (
            stats.verified_tp + stats.verified_fp,
            stats.tables_pruned_rule1 + stats.tables_pruned_rule2,
            stats.tables_evaluated - stats.tables_pruned_rule2,
            stats.tables_pruned_empty,
            stats.regather_pairs,
        )
    for t in range(n_tables):
        if topk.full and int(ptr[t + 1] - ptr[t]) <= topk.bound():
            stats.tables_pruned_rule1 += n_tables - t
            break
        stats.tables_evaluated += 1
        tid = int(block.table_ids[t])
        lo, hi = int(ptr[t]), int(ptr[t + 1])
        # strengthened rule 2: exact filtered-candidate count bound, from the
        # launch's counts — no match-matrix work for pruned tables.
        # Before the heap fills the bound is 0: empty tables are skipped.
        if int(counts[t]) <= topk.bound():
            stats.tables_pruned_rule2 += 1
            stats.tables_pruned_empty += int(not topk.full)
            continue
        if timed:
            t_a = clock()
        if lazy:
            # the filter again, on the table's eligible (row, key) pairs only
            r, kid = _eligible_pairs(plan, block.value_idx[ptr[t] : ptr[t + 1]])
            rsk = (
                row_sk[lo:hi]
                if row_sk is not None
                else index.superkey_of_rows(rows[lo:hi])
            )
            ok = ops.subsume_pairs_np(rsk, plan.q_sk, r, kid)
            sub = _Pairs(r[ok], kid[ok])
            stats.regather_pairs += int(r.size)
            # booked as the slice of the match matrix the pairs stand for
            stats.filter_readback_bytes += (hi - lo) * plan.q_sk.shape[0]
        else:
            sub = hits[lo:hi]
        if timed:
            t_b = clock()
            regather_ns += t_b - t_a
        joinability, mapping = _calculate_j(index, plan, rows[lo:hi], sub)
        if timed:
            exact_ns += clock() - t_b
        topk.offer(tid, joinability, mapping)
    if timed:
        telemetry.count("regather_s", regather_ns * 1e-9)
        telemetry.count("exact_s", exact_ns * 1e-9)
        telemetry.count(
            "pairs_verified", stats.verified_tp + stats.verified_fp - before[0]
        )
        telemetry.count(
            "tables_pruned",
            stats.tables_pruned_rule1 + stats.tables_pruned_rule2 - before[1],
        )
        telemetry.count(
            "tables_pruned_empty", stats.tables_pruned_empty - before[3]
        )
        telemetry.count("regather_pairs", stats.regather_pairs - before[4])
        telemetry.count(
            "tables_verified",
            stats.tables_evaluated - stats.tables_pruned_rule2 - before[2],
        )


@dataclasses.dataclass
class PlanCounts:
    """Phase-A artifact of the two-phase group engine: one request's plan
    plus everything the shared filter launch produced for it — the seam the
    serving tier's hot-table bound cache stores (``serve.cache.BoundCache``).

    ``counts`` is the per-table eligible-hit count vector driving rule-1/2
    pruning; ``hits`` is this plan's slice of the group match matrix (None
    on the fused counts-only path, and always None once cached — see
    ``cacheable``); ``row_sk`` keeps the FULL-width row super keys so a
    dropped/absent matrix is recomputed lazily during scoring,
    bit-identically.  On the GATHER-fused launch ``row_sk`` is None too —
    the host never gathered the superkeys — and scoring gathers surviving
    tables' slices from the index store instead, which is why ``epoch``
    matters doubly there: the store read at scoring time must be the store
    the launch filtered against.  ``epoch`` pins ``MateIndex.mutation_epoch``
    at launch time: a PlanCounts is replayable only while the index is
    unchanged.
    """

    plan: QueryPlan
    row_sk: np.ndarray | None  # uint32[n_items, lanes] full-width row super
    # keys (None: gather-fused launch — scoring reads the index store)
    counts: np.ndarray  # int32[n_tables] per-table eligible-hit counts
    hits: np.ndarray | None = None  # bool[n_items, n_keys]: the host match
    # matrix's block of this plan's rows and keys
    group_keys: int = 0  # key count of the SHARED launch (accounting)
    fused: bool = False  # counts-only fused launch (no matrix anywhere)
    filter_lanes: int = 0  # lanes the launch probed (< index width: degraded)
    epoch: int = 0  # index.mutation_epoch at launch time
    gather_saved: int = 0  # HBM bytes the gather-fused launch never moved
    route_launches: int = 0  # routed index: shard launches this request's
    # items spanned (distinct owning shards — whole-table ownership means
    # each of its candidate tables was counted on exactly one of them)
    route_bytes: int = 0  # routed index: this request's share of the
    # cross-shard count-merge bytes (its counts vector × shards touched)
    # the shared launch's demotions off the gather-fused path, charged to
    # every request of the group (same fields on DiscoveryStats)
    gather_demotions: int = 0
    shard_gather_demotions: int = 0

    def cacheable(self) -> "PlanCounts":
        """A copy safe to hold in a cache: the match-matrix slice is
        dropped; scoring recomputes surviving tables' slices from ``row_sk``
        on demand — same subsumption predicate, so verification inputs (and
        the top-k) are bit-identical."""
        return dataclasses.replace(self, hits=None)


def plan_and_count(
    index: MateIndex,
    queries: list[tuple[Table, list[int]]],
    backend: Backend | str | None = None,
    *,
    init_mode: str = "cardinality",
    filter_lanes: int | None = None,
    fused_block_n: int | None = None,
    profile_gate: bool = False,
    rids: list[int] | None = None,
) -> list[PlanCounts]:
    """Phase A of ``discover_many``: plan every request, then run the ONE
    shared filter launch and demux it into per-request ``PlanCounts``.
    ``rids`` label each request's ``plan.query`` span (serving request ids).

    ``profile_gate=True`` applies the column-profile gate per plan (see
    ``plan_query``) before the shared launch is assembled, so gated tables
    never contribute rows to the group matrix at all.

    Everything up to (and including) ``gather_candidates`` + the §6.3
    filter lives here; ``score_from_counts`` is phase B (pruning, exact
    verification, the heap).  The split is the serving tier's bound-cache
    seam: a hot query's ``PlanCounts`` can be stored and re-scored later —
    at a different ``k`` even — without touching the index or the device.

    ``filter_lanes`` runs the launch over only the first N uint32 lanes of
    the super keys (the serving tier's pressure-degrade path:
    ``filter_lanes=4`` ≙ 128-bit filtering on a wider index).  A lane-prefix
    subsumption test is a pure relaxation of the full-width test — zero
    false negatives — so after exact verification the top-k is
    BIT-IDENTICAL to the full-width run; only filter precision (and the
    rule-2 bound tightness) degrades.
    """
    bk = registry.resolve_backend(backend)
    plans = [
        plan_query(
            index, q, q_cols, init_mode, profile_gate=profile_gate,
            rid=None if rids is None else rids[i],
        )
        for i, (q, q_cols) in enumerate(queries)
    ]
    if not plans:
        return []
    with telemetry.span("filter.assemble"):
        rows_all = np.concatenate([p.block.rows for p in plans])
        q_all = np.concatenate([p.q_sk for p in plans])
        # block-diagonal eligibility (a request's keys only probe its own
        # candidate rows): each plan's init-value ids are offset past the
        # values of the plans before it, so no id is shared across requests;
        # + a global per-item table index for the one-pass per-table
        # rule-1/2 count reduction.
        item_value, key_value = [], []
        seg_all = np.zeros(rows_all.shape[0], dtype=np.int32)
        r_off = v_off = 0
        n_tables_all = 0
        for p in plans:
            ni, ti = p.block.n_items, p.block.n_tables
            item_value.append(p.elig.item_value + v_off)
            key_value.append(p.elig.key_value + v_off)
            if ni:
                seg_all[r_off : r_off + ni] = n_tables_all + _segment_ids(
                    p.block.table_ptr
                )
            r_off += ni
            v_off += p.value_key_ptr.size - 1
            n_tables_all += ti
        group_elig = ops.Eligibility(
            np.concatenate(item_value), np.concatenate(key_value)
        )
    full_lanes = index.cfg.lanes
    fl = full_lanes if filter_lanes is None else max(1, min(int(filter_lanes), full_lanes))
    q_f = q_all if fl == full_lanes else q_all[:, :fl]
    routed = getattr(index, "routed", False)
    use_gather = (
        not routed and bk.gather and ops.gather_store_fits(index.superkeys)
    )
    # gather-fused group launch: no host superkey gather at all — the kernel
    # pulls every request's candidate rows from the device store, and phase B
    # re-gathers only surviving tables' slices (bit-identical: same array).
    # The routed group launch shares that contract (row_sk stays None) and
    # scoring re-gathers from the OWNING shard only.
    row_sk_all = (
        None if (use_gather or routed) else index.superkey_of_rows(rows_all)
    )
    row_f = (
        None if row_sk_all is None
        else row_sk_all if fl == full_lanes else row_sk_all[:, :fl]
    )
    # launch-level counters: only the demotions are read back from it (the
    # routed byte/launch accounting is attributed per request below)
    group = DiscoveryStats()
    group.gather_demotions = int(not routed and bk.gather and not use_gather)
    if routed:
        # shard-local counts-only launches for the whole group; per-request
        # routing accounting is attributed below from each plan's own items.
        hits_all = None
        counts_all = index.routed_counts(
            rows_all, q_f, group_elig, seg_all, n_tables_all,
            backend=bk, fused_block_n=fused_block_n, stats=group,
        )
    elif use_gather:
        hits_all, counts_all = ops.filter_hits_table_counts(
            None, q_f, group_elig, seg_all, n_tables_all,
            backend=bk, fused_block_n=fused_block_n,
            store=index.device_store(), rows=rows_all,
        )
    elif bk.fused:
        # ONE fused filter+segment-count launch for the whole group: the
        # (Σ rows × Σ keys) matrix is never materialised; only the group
        # counts vector is read back.  Surviving tables recompute their
        # own-keys hit slices lazily in _score_tables (bit-identical to
        # slicing the block-diagonal of the full matrix, since eligibility
        # already restricts each row to its own request's keys).
        hits_all, counts_all = ops.filter_hits_table_counts(
            row_f, q_f, group_elig, seg_all, n_tables_all,
            backend=bk, fused_block_n=fused_block_n,
        )
    else:
        # ONE subsumption launch for the whole group.  Every request
        # starts with an empty heap (entry bound 0), so most plans' hit
        # blocks are needed for verification — the matrix comes back to
        # the host in one transfer and the per-table rule-1/2 counts are a
        # cheap host reduction over it.
        hits_all, counts_all = _hits_counts_host(
            row_f, q_f, group_elig, seg_all, n_tables_all, bk,
        )
    epoch = index.mutation_epoch
    out: list[PlanCounts] = []
    r_off = k_off = t_off = 0
    for p in plans:
        ni, ki, ti = p.block.n_items, p.q_sk.shape[0], p.block.n_tables
        # routed attribution: the shards THIS request's items spanned — its
        # solo cost, and (by whole-table ownership) exactly the shards that
        # produced its slice of the group counts vector.
        n_sh = (
            len(np.unique(index._shard_ids_of_rows(p.block.rows)))
            if routed and ni
            else 0
        )
        out.append(
            PlanCounts(
                plan=p,
                row_sk=(
                    None if row_sk_all is None
                    else row_sk_all[r_off : r_off + ni]
                ),
                counts=counts_all[t_off : t_off + ti],
                hits=None if hits_all is None
                else hits_all[r_off : r_off + ni, k_off : k_off + ki],
                group_keys=0 if hits_all is None else int(hits_all.shape[1]),
                fused=hits_all is None,
                filter_lanes=fl,
                epoch=epoch,
                gather_saved=ni * (fl * 4 - 4) if use_gather else 0,
                route_launches=n_sh,
                route_bytes=n_sh * ti * 4,
                gather_demotions=group.gather_demotions,
                shard_gather_demotions=group.shard_gather_demotions,
            )
        )
        r_off += ni
        k_off += ki
        t_off += ti
    return out


def _scoring_plan(pc: PlanCounts, *, charge_launch: bool = True) -> QueryPlan:
    """A copy of ``pc``'s plan whose stats (a FRESH copy too) carry what
    phase A did for the request — so one ``PlanCounts`` can be scored any
    number of times.  ``charge_launch`` adds the request's share of the
    shared launch's transfers: on the fused counts-only launch its counts
    vector (and its gather, routing and demotion counters); otherwise its
    rows against the GROUP's keys, read back to the host — the documented
    cross-product trade."""
    plan = dataclasses.replace(pc.plan, stats=dataclasses.replace(pc.plan.stats))
    stats, block = plan.stats, plan.block
    n_items = block.n_items
    stats.pl_items_checked = n_items
    stats.filter_checks = eligible_count(plan, block.value_idx)
    stats.filter_passed = int(pc.counts.sum())
    stats.filter_lanes = pc.filter_lanes
    if not charge_launch:
        return plan
    if pc.fused:
        stats.filter_fused_launches += 1
        stats.filter_readback_bytes += pc.counts.nbytes
        stats.gather_bytes_saved += pc.gather_saved
        stats.shard_launches += pc.route_launches
        stats.route_bytes_merged += pc.route_bytes
        stats.gather_demotions += pc.gather_demotions
        stats.shard_gather_demotions += pc.shard_gather_demotions
    else:
        stats.filter_matrix_bytes += n_items * pc.group_keys
        stats.filter_readback_bytes += n_items * pc.group_keys
    return plan


def score_from_counts(
    index: MateIndex,
    pc: PlanCounts,
    k: int = 10,
    *,
    from_cache: bool = False,
    rank: str = "count",
) -> tuple[list[TopKEntry], DiscoveryStats]:
    """Phase B of ``discover_many``: rule-1/2 pruning + exact verification
    + the top-k heap over one request's ``PlanCounts``.

    ``rank='quality'`` runs ONE scoring launch over the plan's full counts
    vector (phase A already produced it — no extra filter work) and orders
    the returned entries by join quality; the heap itself is untouched, so
    cached replays at either rank verify the same set.

    Re-runnable: stats land on a FRESH copy of the plan's, so the same
    PlanCounts (a bound-cache hit) can be scored any number of times — at
    any ``k``.  ``from_cache=True`` skips the launch-transfer accounting
    (an earlier request already paid for the filter) and forces the
    lazy-recompute path, since cached entries hold no matrix slice.
    """
    plan = _scoring_plan(pc, charge_launch=not from_cache)
    stats, block = plan.stats, plan.block
    scores: dict[int, float] = {}
    if rank == "quality" and block.n_tables:
        with telemetry.span("score.rank"):
            q_sketch = ranking.query_sketch(index, plan.distinct_keys)
            sc = ranking.quality_scores(
                index, block.table_ids, np.asarray(pc.counts),
                len(plan.distinct_keys), q_sketch, stats=stats,
            )
            scores = dict(zip(block.table_ids.tolist(), sc.tolist()))
    topk = _TopK(k)
    with telemetry.span("score.tables"):
        _score_tables(
            index, plan, topk, None if from_cache else pc.hits, pc.counts,
            row_sk=pc.row_sk,
        )
    return _ranked_entries(topk, rank, scores), stats


def discover_many(
    index: MateIndex,
    queries: list[tuple[Table, list[int]]],
    k: int | list[int] = 10,
    init_mode: str = "cardinality",
    backend: Backend | str | None = None,
    *,
    fused_block_n: int | None = None,
    filter_lanes: int | None = None,
    rank: str = "count",
    profile_gate: bool = False,
) -> list[tuple[list[TopKEntry], DiscoveryStats]]:
    """Multi-query discovery sharing ONE filter launch: ``plan_and_count``
    (phase A: the shared launch) composed with ``score_from_counts``
    (phase B: per-request scoring).  One request without a session is
    ``discover_many(index, [(query, q_cols)], ...)[0]``.

    All requests' candidate rows and query keys concatenate into a single
    subsumption launch; the match matrix (or, on the fused backends, just
    its per-table counts) is then demuxed per request and scored with the
    same rule-1/rule-2 + heap semantics, so each request's top-k is
    bit-identical to its scalar Algorithm 1 run (``core.discovery``).

    ``backend`` selects the §6.3 filter implementation (a resolved
    ``kernels.registry.Backend`` or a registered name); None follows the
    registry precedence: ``MATE_FILTER_BACKEND`` env var, then the platform
    default (fused-gather on TPU, size-based auto split elsewhere).  The
    pre-registry ``use_kernel=``/``fused=`` keywords are gone: passing them
    raises TypeError (``use_kernel=False`` -> 'numpy', ``fused=True`` ->
    'fused', ``fused=False`` -> 'pallas').

    ``rank``/``profile_gate`` thread through both phases: the gate shrinks
    each request's candidate block before the shared launch, quality
    ranking adds one scoring launch per request — neither changes the
    verified set.  These raw functions default both off (``rank='count'``,
    no gate); ``session.DiscoveryConfig`` turns both on at the session
    layer.

    Cost note: the shared launch computes the full (Σ rows × Σ keys) cross
    product — only the block diagonal is consumed, so filter work grows
    ~linearly with group size beyond the useful probes.  That trade buys one
    kernel dispatch for the whole group, which wins while dispatch latency
    dominates (small/medium groups, accelerator backends); keep serving
    groups bounded (``DiscoveryEngine(batch=...)``, default 8) rather than
    fusing unbounded request sets.
    """
    ks = [k] * len(queries) if isinstance(k, int) else list(k)
    assert len(ks) == len(queries)
    pcs = plan_and_count(
        index, queries, backend,
        init_mode=init_mode, filter_lanes=filter_lanes,
        fused_block_n=fused_block_n, profile_gate=profile_gate,
    )
    return [
        score_from_counts(index, pc, k_i, rank=rank) for pc, k_i in zip(pcs, ks)
    ]


def filter_outcomes(
    index: MateIndex,
    query: Table,
    q_cols: list[int],
    init_mode: str = "cardinality",
    check_false_negatives: bool = False,
) -> dict[str, int]:
    """Unpruned §6.3 filter quality for one query — the paper's Table 1/2
    false-positive measurement at whatever hash width the index was built at.

    Every eligible (candidate row, query key) pair is probed through the
    super-key filter and every surviving pair is verified exactly; no top-k
    pruning interferes, so counts are a property of the hash alone.

    Returns counts: ``checks`` (eligible probes), ``passed`` (filter
    survivors), ``tp`` / ``fp`` (survivors that pass / fail exact key
    comparison), and — when ``check_false_negatives`` — ``fn``: eligible
    pairs that verify exactly but were REJECTED by the filter (always 0 for
    any OR-aggregated hash; the §6.3 no-false-negative lemma).
    """
    plan = plan_query(index, query, q_cols, init_mode)
    out = {
        "checks": eligible_count(plan, plan.block.value_idx),
        "passed": 0,
        "tp": 0,
        "fp": 0,
        "fn": 0,
        "items": plan.block.n_items,
        "keys": len(plan.distinct_keys),
    }
    if plan.block.n_items == 0 or not plan.distinct_keys:
        return out
    row_sk = index.superkey_of_rows(plan.block.rows)
    elig = plan.elig.dense()
    hits = ops.subsume_np(row_sk, plan.q_sk) & elig
    out["passed"] = int(hits.sum())
    corpus = index.corpus
    row_values_cache: dict[int, list[str]] = {}

    def _matches(r: int, kid: int) -> bool:
        grow = int(plan.block.rows[r])
        vals = row_values_cache.get(grow)
        if vals is None:
            vals = row_values_cache[grow] = corpus.row_values(grow)
        return bool(seq._verify_pair(plan.distinct_keys[kid], vals))

    for r, kid in zip(*np.nonzero(hits)):
        if _matches(int(r), int(kid)):
            out["tp"] += 1
        else:
            out["fp"] += 1
    if check_false_negatives:
        for r, kid in zip(*np.nonzero(elig & ~hits)):
            if _matches(int(r), int(kid)):
                out["fn"] += 1
    return out
