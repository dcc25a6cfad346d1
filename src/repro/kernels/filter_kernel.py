"""Pallas TPU kernel for the super-key row filter (paper §6.3).

This is MATE's hot loop: for every (candidate row, query key) pair test
``(q & ~row) == 0`` over the hash lanes.  On TPU this is a pure-VPU
streaming workload; the kernel tiles both operands into VMEM and emits either
the match matrix, a fused per-query count, or a fused per-TABLE segment count
(``filter_table_counts``: subsumption ∧ eligibility row-summed and
scatter-accumulated over the CSR table ids — the reduction happens in VMEM,
the n×q matrix never reaches HBM, which is what makes the filter
memory-roofline-optimal: 16 bytes read per row, 4 bytes written per table).

Layout note: super keys live on the host as ``uint32[n, lanes]``; lanes is
tiny (4 for 128-bit hashes) and would be a terrible minor-most dim for the
8×128 VREG tiling, so the wrappers in ops.py transpose candidate blocks to
``[lanes, n]`` before the call — each lane row is then a well-formed
128-aligned vector.  The gather kernel's device-resident store is instead
packed into whole 128-lane lines (see ``_gather_counts_kernel``).  Per-row
vectors (table ids, row offsets, init-value ids) travel as ``[1, n]`` rows
and become ``[bn, 1]`` columns by an int32 reshape inside the kernels:
Mosaic cannot reshape a bool vector into a column.  Eligibility arrives as
two such id vectors, one per row and one per query key, and each tile forms
its own ``[bn, bq]`` mask from them: no ``[n, q]`` eligibility operand
exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 1024
DEFAULT_BLOCK_Q = 256

# The fused count kernel's one-hot scatter tile is [block_n, tb] f32; keep it
# within ~4 MiB of VMEM.  At the table cap the block floor (128, the lane-dim
# tiling minimum) sits exactly on budget: 128 · 8192 · 4 B = 4 MiB.
FUSED_ONEHOT_BUDGET = 1 << 20  # block_n · tb elements
FUSED_MAX_TABLES = 8192


def fused_block_n(n_tables_padded: int, cap: int = DEFAULT_BLOCK_N) -> int:
    """Row-block size for ``filter_table_counts``: the largest power of two
    ≤ ``cap`` keeping the one-hot tile within FUSED_ONEHOT_BUDGET, floored at
    128.  Power-of-two so it divides every padded row count the wrappers
    produce (pow2 buckets below 8192, multiples of 8192 above)."""
    b = 128
    while b * 2 <= cap and (b * 2) * n_tables_padded <= FUSED_ONEHOT_BUDGET:
        b *= 2
    return b


def _match_kernel(row_ref, query_ref, out_ref, *, lanes: int):
    """row_ref: uint32[lanes, bn]; query_ref: uint32[lanes, bq];
    out_ref: int8[bn, bq]."""
    acc = None
    for lane in range(lanes):
        r = row_ref[lane, :]  # [bn]
        q = query_ref[lane, :]  # [bq]
        ok = (q[None, :] & ~r[:, None]) == 0  # [bn, bq]
        acc = ok if acc is None else (acc & ok)
    out_ref[...] = acc.astype(jnp.int8)


def _count_kernel(row_ref, query_ref, out_ref, *, lanes: int, n_blocks: int):
    """Fused filter+count: accumulates per-query candidate counts over the
    row-block grid axis. out_ref: int32[bq]."""
    i = pl.program_id(1)  # row-block index (inner grid axis)
    acc = None
    for lane in range(lanes):
        r = row_ref[lane, :]
        q = query_ref[lane, :]
        ok = (q[None, :] & ~r[:, None]) == 0
        acc = ok if acc is None else (acc & ok)
    partial = jnp.sum(acc.astype(jnp.int32), axis=0)  # [bq]

    @pl.when(i == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(i != 0)
    def _accum():
        out_ref[...] += partial


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_q", "interpret")
)
def filter_match(
    row_sk_t: jnp.ndarray,
    query_sk_t: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_q: int = DEFAULT_BLOCK_Q,
    interpret: bool = False,
) -> jnp.ndarray:
    """Match matrix from transposed super keys.

    Args:
      row_sk_t:   uint32[lanes, n] (n divisible by block_n).
      query_sk_t: uint32[lanes, q] (q divisible by block_q).
    Returns:
      int8[n, q].
    """
    lanes, n = row_sk_t.shape
    _, q = query_sk_t.shape
    grid = (n // block_n, q // block_q)
    return pl.pallas_call(
        functools.partial(_match_kernel, lanes=lanes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((lanes, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((lanes, block_q), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_q), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, q), jnp.int8),
        interpret=interpret,
        name="filter_match",
    )(row_sk_t, query_sk_t)


def _scatter_counts(acc, seg, counts_ref, first, mode: str = "sum"):
    """Row-reduce a masked [bn, bq] hit tile and scatter it into per-table
    counts: the one-hot f32 matvec shared by both fused kernels.

    ``seg`` is the int32[bn, 1] table-id column (-1 matches no iota column,
    so padding rows contribute 0).  The one-hot [bn, tb] is contracted over
    its row axis on the MXU; f32 accumulation is exact here (per-step
    partials are bounded by bn·bq « 2^24)."""
    per_row = jnp.sum(acc.astype(jnp.int32), axis=1, keepdims=True)  # [bn, 1]
    if mode == "any":
        per_row = (per_row > 0).astype(jnp.int32)
    bn, tb = acc.shape[0], counts_ref.shape[1]
    onehot = seg == jax.lax.broadcasted_iota(jnp.int32, (bn, tb), 1)
    partial = jax.lax.dot_general(
        per_row.astype(jnp.float32),
        onehot.astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)  # [1, tb]

    @pl.when(first)
    def _init_counts():
        counts_ref[...] = partial

    @pl.when(jnp.logical_not(first))
    def _accum_counts():
        counts_ref[...] += partial


def _mask_tile(acc, elig_refs, seg, j, n_queries: int):
    """Eligibility, padded-query-column and padding-row masks of a hit tile.

    ``elig_refs`` is None (all eligible) or the pair (item value ids
    int32[1, bn], key value ids int32[1, bq]): the eligibility tile is
    ``item == key``, formed here from the two id blocks.  Padded query
    columns (col id ≥ n_queries) carry all-ones super keys that match
    nothing EXCEPT saturated (all-ones) row super keys, which would
    otherwise be overcounted when no eligibility ids mask them."""
    bn, bq = acc.shape
    if elig_refs is not None:
        item_ref, kval_ref = elig_refs
        acc = acc & (item_ref[...].reshape(bn, 1) == kval_ref[...])
    col = j * bq + jax.lax.broadcasted_iota(jnp.int32, (bn, bq), 1)
    return acc & (col < n_queries) & (seg >= 0)


def _table_counts_kernel(
    *refs, lanes: int, mode: str, has_elig: bool, n_queries: int
):
    """Fused filter + segment-count: subsumption ∧ eligibility, row-summed and
    scatter-accumulated into per-table counts via the CSR segment ids — the
    [bn, bq] match tile lives only in VREGs/VMEM and is reduced before the
    next grid step, so the n×q matrix never reaches HBM.

    Refs (has_elig controls arity):
      row_ref:    uint32[lanes, bn]   candidate-row super keys (transposed)
      query_ref:  uint32[lanes, bq]   query-key super keys (transposed)
      item_ref:   int32[1, bn]        item init-value ids (only when has_elig)
      kval_ref:   int32[1, bq]        key init-value ids (only when has_elig)
      seg_ref:    int32[1, bn]        table index per row; -1 = padding row
      counts_ref: int32[1, tb]        per-table counts (ONE block, all steps)
      key_ref:    int32[1, bq]        per-key survivor counts

    A [n, 1] seg (or item id) operand would also compile, but XLA would
    relay it out lane-padded (128× its bytes) before every launch.

    ``mode``: 'sum' counts eligible (row, key) hits per table (the engines'
    exact rule-2 bound); 'any' counts rows matching ≥1 key (the distributed
    filter's per-table semantics — requires a single query block, since
    per-block ORs cannot be summed across query blocks).
    """
    if has_elig:
        row_ref, query_ref, item_ref, kval_ref, seg_ref, counts_ref, key_ref = refs
        elig_refs = (item_ref, kval_ref)
    else:
        row_ref, query_ref, seg_ref, counts_ref, key_ref = refs
        elig_refs = None
    j = pl.program_id(0)  # query-block index
    i = pl.program_id(1)  # row-block index (inner grid axis → sequential)
    bn = row_ref.shape[1]
    acc = None
    for lane in range(lanes):
        r = row_ref[lane, :][:, None]  # [bn, 1]
        q = query_ref[lane : lane + 1, :]  # [1, bq]
        ok = (q & ~r) == 0  # [bn, bq]
        acc = ok if acc is None else (acc & ok)
    seg = seg_ref[...].reshape(bn, 1)
    acc = _mask_tile(acc, elig_refs, seg, j, n_queries)
    key_partial = jnp.sum(acc.astype(jnp.int32), axis=0, keepdims=True)
    _scatter_counts(
        acc, seg, counts_ref, jnp.logical_and(i == 0, j == 0), mode
    )

    @pl.when(i == 0)
    def _init_keys():
        key_ref[...] = key_partial

    @pl.when(i != 0)
    def _accum_keys():
        key_ref[...] += key_partial


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_tables", "n_queries", "block_n", "block_q", "mode", "interpret"
    ),
)
def filter_table_counts(
    row_sk_t: jnp.ndarray,
    query_sk_t: jnp.ndarray,
    elig: tuple[jnp.ndarray, jnp.ndarray] | None,
    seg_ids: jnp.ndarray,
    *,
    n_tables: int,
    n_queries: int | None = None,
    block_n: int = DEFAULT_BLOCK_N,
    block_q: int = DEFAULT_BLOCK_Q,
    mode: str = "sum",
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused filter + per-table segment count from transposed super keys.

    Args:
      row_sk_t:   uint32[lanes, n] (n divisible by block_n).
      query_sk_t: uint32[lanes, q] (q divisible by block_q).
      elig:       (item_value int32[n], key_value int32[q]) init-value ids,
                  row i and query k eligible where they are equal; or None
                  for all-eligible.
      seg_ids:    int32[n] table index per row (-1 for padding rows).
      n_tables:   padded table count tb (multiple of 128).
      n_queries:  number of REAL queries (≤ q); columns beyond it are
                  padding and contribute nothing even to saturated
                  (all-ones) row super keys.  Defaults to q.
    Returns:
      (counts int32[tb], key_counts int32[q]) — the ONLY outputs; the n×q
      match matrix is never materialised.
    """
    assert mode in ("sum", "any")
    lanes, n = row_sk_t.shape
    _, q = query_sk_t.shape
    n_queries = q if n_queries is None else n_queries
    if mode == "any":
        # per-row ANY cannot be accumulated across query blocks
        assert q == block_q, "mode='any' needs the whole query range in one block"
    grid = (q // block_q, n // block_n)  # row axis INNER → sequential accum
    in_specs = [
        pl.BlockSpec((lanes, block_n), lambda j, i: (0, i)),
        pl.BlockSpec((lanes, block_q), lambda j, i: (0, j)),
    ]
    operands = [row_sk_t, query_sk_t]
    if elig is not None:
        item_value, key_value = elig
        in_specs.append(pl.BlockSpec((1, block_n), lambda j, i: (0, i)))
        in_specs.append(pl.BlockSpec((1, block_q), lambda j, i: (0, j)))
        operands += [item_value.reshape(1, n), key_value.reshape(1, q)]
    in_specs.append(pl.BlockSpec((1, block_n), lambda j, i: (0, i)))
    operands.append(seg_ids.reshape(1, n))
    counts, key_counts = pl.pallas_call(
        functools.partial(
            _table_counts_kernel,
            lanes=lanes,
            mode=mode,
            has_elig=elig is not None,
            n_queries=n_queries,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, n_tables), lambda j, i: (0, 0)),
            pl.BlockSpec((1, block_q), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_tables), jnp.int32),
            jax.ShapeDtypeStruct((1, q), jnp.int32),
        ],
        interpret=interpret,
        name="filter_table_counts",
    )(*operands)
    return counts[0], key_counts[0]


# the packed store's line width: one (8, 128)-tiled uint32 lane row
STORE_LINE = 128


def _gather_counts_kernel(
    *refs, lanes: int, store_lanes: int, has_elig: bool, n_queries: int,
    block_n: int,
):
    """Gather-fused filter + segment-count: one launch from posting-list row
    offsets to per-table counts.

    The candidate rows' super keys are DMA-gathered from the device-resident
    PACKED store (HBM, ``memory_space=ANY``) straight into a VMEM scratch
    tile — the rows×lanes candidate block never exists in HBM, and the host
    never gathers (or ships) it at all.  The store is the row-major
    superkey array reshaped to ``uint32[n_lines, 128]``: line ``r // per``
    holds row ``r`` at columns ``(r % per)·store_lanes`` onward, with
    ``per = 128 // store_lanes``.  Each DMA copies the one 512-byte line
    holding its row (a DMA's minor extent must be the whole 128-lane tile
    row); the row's lanes are then shifted to the tile's first columns by a
    masked rotate-and-add fold, once per row block.

    Refs (has_elig sets arity):
      rows_smem:  int32[1, bn]        row offsets (SMEM: DMA addresses)
      rows_ref:   int32[1, bn]        the same offsets (VMEM: lane offsets)
      store_ref:  uint32[n_lines, 128] packed super-key store (HBM/ANY)
      query_ref:  uint32[lanes, bq]   query-key super keys (transposed)
      item_ref:   int32[1, bn]        item init-value ids (only when has_elig)
      kval_ref:   int32[1, bq]        key init-value ids (only when has_elig)
      seg_ref:    int32[1, bn]        table index per row; -1 = padding row
      counts_ref: int32[1, tb]        per-table counts (ONE block, all steps)
      line_vmem:  uint32[bn, 128]     gathered store lines
      key_vmem:   int32[bn, 128]      each row's lanes in columns 0..lanes-1
      sem:        DMA semaphore for the gather copies

    Grid is (row blocks, query blocks) with the QUERY axis innermost, the
    transpose of ``_table_counts_kernel``'s grid: the gather runs once per
    row block (at ``j == 0``) and the scratch tile is reused across the
    query-block sweep.  That ordering is only possible because this kernel
    has no per-key output — per-key counts would need consecutive row steps
    per query block — so it emits per-table counts alone ('sum' semantics).

    ``lanes`` is the number of lanes PROBED (== the query operand's lane
    count).  It may be smaller than ``store_lanes`` (the serving tier's
    lane-prefix degrade): only the first ``lanes`` of each row are picked.
    """
    rows_smem, rows_ref, store_ref, query_ref = refs[:4]
    if has_elig:
        elig_refs, refs = refs[4:6], refs[6:]
    else:
        elig_refs, refs = None, refs[4:]
    seg_ref, counts_ref, line_vmem, key_vmem, sem = refs
    i = pl.program_id(0)  # row-block index (outer)
    j = pl.program_id(1)  # query-block index (inner → scratch reuse across j)
    per_line = STORE_LINE // store_lanes  # rows per line, a power of two
    slot_bits = per_line.bit_length() - 1
    lane_bits = store_lanes.bit_length() - 1

    def _copy(r):
        line = rows_smem[0, r] >> slot_bits
        return pltpu.make_async_copy(
            store_ref.at[pl.ds(line, 1)], line_vmem.at[pl.ds(r, 1)], sem
        )

    @pl.when(j == 0)
    def _gather():
        # all copies are issued back-to-back, then drained — the per-row
        # latency overlaps across the outstanding queue.
        def _start(r, _):
            _copy(r).start()
            return 0

        jax.lax.fori_loop(0, block_n, _start, 0)

        def _wait(r, _):
            _copy(r).wait()
            return 0

        jax.lax.fori_loop(0, block_n, _wait, 0)
        # keep each row's own slot of its line, then fold the line onto its
        # first store_lanes columns: summing all its rotations by multiples
        # of store_lanes adds only zeros to the kept slot.  int32 views:
        # Mosaic rotates and reduces no unsigned type, and the subsumption
        # test below is bitwise, so it reads the same bits either way.
        lines = jax.lax.bitcast_convert_type(line_vmem[...], jnp.int32)
        slot = (rows_ref[...] & (per_line - 1)).reshape(block_n, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, lines.shape, 1)
        keys = jnp.where((col >> lane_bits) == slot, lines, 0)
        step = STORE_LINE // 2
        while step >= store_lanes:
            keys = keys + pltpu.roll(keys, step, 1)
            step //= 2
        key_vmem[...] = keys

    acc = None
    for lane in range(lanes):
        r = key_vmem[:, lane : lane + 1]  # [bn, 1]
        q = jax.lax.bitcast_convert_type(
            query_ref[lane : lane + 1, :], jnp.int32
        )  # [1, bq]
        ok = (q & ~r) == 0  # [bn, bq]
        acc = ok if acc is None else (acc & ok)
    seg = seg_ref[...].reshape(block_n, 1)
    acc = _mask_tile(acc, elig_refs, seg, j, n_queries)
    _scatter_counts(acc, seg, counts_ref, jnp.logical_and(i == 0, j == 0))


@functools.partial(
    jax.jit,
    static_argnames=(
        "store_lanes", "n_tables", "n_queries", "block_n", "block_q",
        "interpret",
    ),
)
def gather_filter_table_counts(
    rows: jnp.ndarray,
    store: jnp.ndarray,
    query_sk_t: jnp.ndarray,
    elig: tuple[jnp.ndarray, jnp.ndarray] | None,
    seg_ids: jnp.ndarray,
    *,
    store_lanes: int,
    n_tables: int,
    n_queries: int | None = None,
    block_n: int = DEFAULT_BLOCK_N,
    block_q: int = DEFAULT_BLOCK_Q,
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather-fused filter + per-table segment count.

    One launch from posting-list offsets to counts: each grid step
    DMA-gathers its row block's store lines into VMEM before the fused
    subsume ∧ eligibility + reduce + scatter — the gathered rows×lanes
    block never touches HBM.  The offsets reach SMEM one [1, block_n] block per grid
    step, so no launch-sized operand has to fit in SMEM.

    Args:
      rows:       int32[n] row offsets into the store (n divisible by
                  block_n; padding offsets must be valid, e.g. 0, and carry
                  seg id -1).
      store:      uint32[n_lines, 128] packed super-key store (see
                  ``_gather_counts_kernel``), ``store_lanes`` lanes per row.
      query_sk_t: uint32[lanes, q] transposed query super keys (q divisible
                  by block_q); ``lanes <= store_lanes`` — a strict prefix
                  probes a lane-degraded filter over the full-width store.
      elig:       (item_value int32[n], key_value int32[q]) init-value ids
                  (see ``filter_table_counts``), or None for all-eligible.
      seg_ids:    int32[n] table index per row (-1 for padding rows).
      n_tables:   padded table count tb (multiple of 128).
      n_queries:  number of REAL queries (≤ q).
    Returns:
      counts int32[tb] — the ONLY output (no per-key counts: the grid runs
      query-blocks innermost so the gather amortises over them, which rules
      out the per-key accumulation layout of ``filter_table_counts``).
    """
    lanes, q = query_sk_t.shape
    n = rows.shape[0]
    assert lanes <= store_lanes and STORE_LINE % store_lanes == 0
    assert store.shape[1] == STORE_LINE, store.shape
    n_queries = q if n_queries is None else n_queries
    grid = (n // block_n, q // block_q)  # query axis INNER → scratch reuse
    rows2 = rows.reshape(1, n)
    in_specs = [
        pl.BlockSpec(
            (1, block_n), lambda i, j: (0, i), memory_space=pltpu.SMEM
        ),
        pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        pl.BlockSpec(memory_space=pl.ANY),  # store stays in HBM
        pl.BlockSpec((lanes, block_q), lambda i, j: (0, j)),
    ]
    operands = [rows2, rows2, store, query_sk_t]
    if elig is not None:
        item_value, key_value = elig
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, i)))
        in_specs.append(pl.BlockSpec((1, block_q), lambda i, j: (0, j)))
        operands += [item_value.reshape(1, n), key_value.reshape(1, q)]
    in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, i)))
    operands.append(seg_ids.reshape(1, n))
    counts = pl.pallas_call(
        functools.partial(
            _gather_counts_kernel,
            lanes=lanes,
            store_lanes=store_lanes,
            has_elig=elig is not None,
            n_queries=n_queries,
            block_n=block_n,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_tables), lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_tables), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((block_n, STORE_LINE), jnp.uint32),
            pltpu.VMEM((block_n, STORE_LINE), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        # the device trace and the benchmark find the kernel by this name
        name="gather_filter_table_counts",
    )(*operands)
    return counts[0]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_q", "interpret")
)
def filter_count(
    row_sk_t: jnp.ndarray,
    query_sk_t: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_q: int = DEFAULT_BLOCK_Q,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused per-query candidate count. Returns int32[q]."""
    lanes, n = row_sk_t.shape
    _, q = query_sk_t.shape
    n_blocks = n // block_n
    grid = (q // block_q, n_blocks)  # row axis INNER → sequential accumulation
    return pl.pallas_call(
        functools.partial(_count_kernel, lanes=lanes, n_blocks=n_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((lanes, block_n), lambda j, i: (0, i)),
            pl.BlockSpec((lanes, block_q), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q,), lambda j, i: (j,)),
        out_shape=jax.ShapeDtypeStruct((q,), jnp.int32),
        interpret=interpret,
        name="filter_count",
    )(row_sk_t, query_sk_t)
