"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, the [n, lanes] <-> [lanes, n] layout
transposes, and interpret-mode selection (``interpret=True`` on CPU hosts so
the kernels run everywhere; on TPU backends the real Mosaic path is used).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import encoding
from repro.core.xash import DEFAULT_CONFIG, XashConfig
from repro.kernels import filter_kernel, registry, xash_kernel
from repro.kernels.registry import Backend


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def fused_filter_default() -> bool:
    """True when the unpinned dispatch resolves to the fused counts-only
    launch (``MATE_FILTER_BACKEND=fused``, or a real TPU where the fused
    kernel is the roofline path).  Selection itself lives in
    ``kernels.registry`` — this is a convenience predicate over it."""
    return registry.resolve_backend().fused


def _pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    target = max(-(-size // multiple) * multiple, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def superkey(
    enc_rows: np.ndarray | jnp.ndarray,
    cfg: XashConfig = DEFAULT_CONFIG,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Super keys of encoded rows. enc: uint8[n, n_cols, max_len] -> uint32[n, lanes]."""
    interpret = _on_cpu() if interpret is None else interpret
    block_n = block_n or xash_kernel.DEFAULT_BLOCK_N
    n = enc_rows.shape[0]
    enc = _pad_to(jnp.asarray(enc_rows, dtype=jnp.int32), 0, block_n)
    rank = jnp.asarray(cfg.freq_rank(), dtype=jnp.int32)[None, :]
    out_t = xash_kernel.xash_superkey(
        enc, rank, cfg, block_n=block_n, interpret=interpret
    )
    return out_t.T[:n]


def xash_values(
    enc_values: np.ndarray | jnp.ndarray,
    cfg: XashConfig = DEFAULT_CONFIG,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-value XASH: uint8[n, max_len] -> uint32[n, lanes] (1-cell rows)."""
    return superkey(jnp.asarray(enc_values)[:, None, :], cfg, interpret=interpret)


# per-shard values per launch: bounds the [chunk, max_len, 37] one-hot
# intermediate of the vectorised hash, mirroring the single-host chunking
# (core.index._XASH_CHUNK)
_MESH_HASH_CHUNK = 1 << 15


def xash_values_mesh(
    enc_values: np.ndarray,
    cfg: XashConfig = DEFAULT_CONFIG,
    *,
    mesh,
    row_axes: tuple[str, ...] | None = None,
    chunk: int = _MESH_HASH_CHUNK,
    times_out: list | None = None,
) -> np.ndarray:
    """Mesh-sharded unique-value XASH: uint8[n, max_len] -> uint32[n, lanes].

    The offline build's throughput-critical pass: values are block-partitioned
    over ``row_axes`` and hashed under ``shard_map`` by the SAME vectorised
    ``core.xash.xash`` the single-host ``MateIndex`` build runs.  Per-value
    hashing has no cross-value term and is pure integer arithmetic, so the
    gathered shard outputs are BIT-IDENTICAL to the single-host pass at any
    device count — the invariant ``tests/test_sharded_build.py`` pins.

    ``chunk`` bounds values-per-shard-per-launch (device memory, see
    ``_MESH_HASH_CHUNK``); padding values hash to all-zero lanes and are
    sliced off.  ``times_out`` (optional list) receives per-launch wall
    seconds for ``BuildStats`` accounting — launches are SPMD-collective, so
    every shard participates in every entry.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed
    from repro.core import xash as xash_lib

    row_axes = tuple(row_axes or mesh.axis_names)
    n_shards = distributed.mesh_shard_count(mesh, row_axes)
    n = enc_values.shape[0]
    out = np.zeros((n, cfg.lanes), dtype=np.uint32)
    if n == 0:
        return out
    sharding = NamedSharding(mesh, P(row_axes))
    hash_fn = jax.jit(
        jax.shard_map(
            lambda e: xash_lib.xash(e, cfg),
            mesh=mesh,
            in_specs=P(row_axes),
            out_specs=P(row_axes),
        )
    )
    import time as _time

    step = chunk * n_shards
    for s in range(0, n, step):
        block = np.asarray(enc_values[s : s + step])
        nb = block.shape[0]
        block = distributed.pad_rows_to_shards(block, n_shards)
        t0 = _time.perf_counter()
        lanes = np.asarray(hash_fn(jax.device_put(block, sharding)))
        if times_out is not None:
            times_out.append(_time.perf_counter() - t0)
        out[s : s + nb] = lanes[:nb]
    return out


def flash_attention(
    q: jnp.ndarray,  # [B, S, H, d]
    k: jnp.ndarray,  # [B, T, H, d]
    v: jnp.ndarray,  # [B, T, H, dv]
    *,
    causal: bool = True,
    window: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas flash attention on [B, S, H, d] layouts (pads S/T to blocks).

    Heads must already be repeated to full count (layers.repeat_kv).
    """
    from repro.kernels import flash_kernel

    interpret = _on_cpu() if interpret is None else interpret
    b, s, h, d = q.shape
    t, dv = k.shape[1], v.shape[3]
    bq, bkv = flash_kernel.DEFAULT_BLOCK_Q, flash_kernel.DEFAULT_BLOCK_KV
    qp = _pad_to(q.transpose(0, 2, 1, 3).reshape(b * h, s, d), 1, bq)
    kp = _pad_to(k.transpose(0, 2, 1, 3).reshape(b * h, t, d), 1, bkv)
    vp = _pad_to(v.transpose(0, 2, 1, 3).reshape(b * h, t, dv), 1, bkv)
    # padded kv rows have position > every real q (masked by causal); for
    # non-causal, mask them via a window trick is unsound — require causal
    # or aligned shapes for non-causal use.
    assert causal or (s % bq == 0 and t % bkv == 0), "non-causal needs aligned shapes"
    out = flash_kernel.flash_attention(
        qp, kp, vp, causal=causal, window=window, interpret=interpret
    )
    return out[:, :s].reshape(b, h, s, dv).transpose(0, 2, 1, 3)


def filter_match(
    row_sk: jnp.ndarray,
    query_sk: jnp.ndarray,
    *,
    block_n: int | None = None,
    block_q: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Subsumption match matrix: (uint32[n, lanes], uint32[q, lanes]) -> bool[n, q].

    Padded rows have super key 0 (subsume only all-zero queries); padded
    queries are sliced off before returning.
    """
    interpret = _on_cpu() if interpret is None else interpret
    block_n = block_n or filter_kernel.DEFAULT_BLOCK_N
    block_q = block_q or filter_kernel.DEFAULT_BLOCK_Q
    n, q = row_sk.shape[0], query_sk.shape[0]
    # pad rows with all-ones superkeys → they match everything; slice off.
    row_t = _pad_to(jnp.asarray(row_sk, jnp.uint32).T, 1, block_n)
    qry_t = _pad_to(jnp.asarray(query_sk, jnp.uint32).T, 1, block_q)
    out = filter_kernel.filter_match(
        row_t, qry_t, block_n=block_n, block_q=block_q, interpret=interpret
    )
    return out[:n, :q].astype(jnp.bool_)


@jax.jit
def _subsume_block(row_sk: jnp.ndarray, query_sk: jnp.ndarray) -> jnp.ndarray:
    """Vectorised XLA subsumption: (uint32[n, lanes], uint32[q, lanes]) -> bool[n, q]."""
    return jnp.all((query_sk[None, :, :] & ~row_sk[:, None, :]) == 0, axis=-1)


def subsume_np(row_sk: np.ndarray, query_sk: np.ndarray) -> np.ndarray:
    """Host-side subsumption oracle (§6.3): bool[n, q].

    The single definition of the filter predicate outside the kernels — the
    engines' numpy paths route here so the semantics can't silently diverge.
    """
    rows = np.ascontiguousarray(row_sk, dtype=np.uint32)
    qry = np.ascontiguousarray(query_sk, dtype=np.uint32)
    if rows.shape[1] % 2 == 0:
        # lane pairs as uint64: the test is bitwise, so the pairs read the
        # same bits in half the passes
        rows, qry = rows.view(np.uint64), qry.view(np.uint64)
    # one [n, q] pass per lane: no [n, q, lanes] temporary
    not_rows = ~rows
    ok = (qry[None, :, 0] & not_rows[:, 0, None]) == 0
    for lane in range(1, rows.shape[1]):
        ok &= (qry[None, :, lane] & not_rows[:, lane, None]) == 0
    return ok


# above this many pairs left, subsume_pairs_np tests one lane at a time:
# where the filter rejects most pairs the first lanes drop them, and on
# large slices the loop beats one pass over every lane by about 2x
_PAIRS_BY_LANE = 1 << 14


def subsume_pairs_np(
    row_sk: np.ndarray, query_sk: np.ndarray, rows: np.ndarray, keys: np.ndarray
) -> np.ndarray:
    """``subsume_np`` on (row, key) pairs alone: bool[n], pair ``i`` tests
    ``row_sk[rows[i]]`` against ``query_sk[keys[i]]``.  While many pairs
    are left, one lane at a time, each lane on the pairs the earlier lanes
    passed; the remaining lanes in one pass."""
    rsk = np.ascontiguousarray(row_sk, dtype=np.uint32)
    qry = np.ascontiguousarray(query_sk, dtype=np.uint32)
    if rsk.shape[1] % 2 == 0:
        rsk, qry = rsk.view(np.uint64), qry.view(np.uint64)
    not_rows = ~rsk
    alive = None  # indices of the pairs the lanes so far passed (None: all)
    r, k, lane = rows, keys, 0
    while lane < rsk.shape[1] and len(r) > _PAIRS_BY_LANE:
        bits = np.take(qry[:, lane], k)
        bits &= np.take(not_rows[:, lane], r)
        sel = np.flatnonzero(bits == 0)
        alive = sel if alive is None else alive[sel]
        r, k, lane = r[sel], k[sel], lane + 1
    ok = ~(qry[k, lane:] & not_rows[r, lane:]).any(axis=1)
    if alive is None:
        return ok
    out = np.zeros(len(rows), dtype=bool)
    out[alive[ok]] = True
    return out


# CPU fallback pads each dim up to a power-of-two bucket so XLA compiles
# O(log) distinct shapes instead of one program per batch size.
_FALLBACK_MIN_N = 512
_FALLBACK_MIN_Q = 64
# below this many (row × key) probes, numpy beats the XLA dispatch latency
_MIN_XLA_PROBES = 1 << 17


def _pow2_bucket(size: int, minimum: int) -> int:
    b = minimum
    while b < size:
        b <<= 1
    return b


# finer bucketing for the fused hits+counts launch: pow2 up to 8k, then 8k
# steps — the padded rows cost real compute (subsume + reductions), and at
# pow2 granularity that waste approaches 2x; still O(few) compiled shapes.
_BUCKET_STEP = 8192


def _bucket(size: int, minimum: int) -> int:
    if size <= _BUCKET_STEP:
        return _pow2_bucket(size, minimum)
    return -(-size // _BUCKET_STEP) * _BUCKET_STEP


def _check_fused_block_n(block_n: int) -> None:
    """Validate a user-facing ``fused_block_n`` override.

    A ``ValueError`` (not an ``assert``) so the check also fires under
    ``python -O`` — the override flows in from ``DiscoveryConfig`` and this
    message mirrors its ``__post_init__`` wording.
    """
    if block_n < 128 or block_n & (block_n - 1):
        raise ValueError(
            f"fused_block_n must be a power of two >= 128, got {block_n}"
        )


# padding ids of the eligibility operands: init-value ids are >= 0, so a
# padding item (-1) or key (-2) is eligible with nothing, not even each other
PAD_ITEM_VALUE = -1
PAD_KEY_VALUE = -2


@dataclasses.dataclass(frozen=True)
class Eligibility:
    """Which (item, key) pairs a filter launch probes, in id form.

    Algorithm 1 probes a key only against the posting list of its init
    value, so item ``i`` and key ``k`` are eligible exactly when
    ``item_value[i] == key_value[k]``.  Launches upload the two id vectors
    and the kernels form each tile's mask from them: no [items, keys]
    eligibility array exists on the host or the device."""

    item_value: np.ndarray  # int32[n] each item's init-value id
    key_value: np.ndarray  # int32[q] each key's init-value id

    def __getitem__(self, items) -> "Eligibility":
        """The eligibility of a subset of the items (a slice, mask or index)."""
        return Eligibility(self.item_value[items], self.key_value)

    def dense(self) -> np.ndarray:
        """bool[n, q]: for host paths that return a hits matrix, and tests."""
        return self.item_value[:, None] == self.key_value[None, :]

    def padded(self, nb: int, qb: int) -> tuple[np.ndarray, np.ndarray]:
        """The two id vectors as int32, padded to ``nb`` items, ``qb`` keys."""
        item = np.full(nb, PAD_ITEM_VALUE, dtype=np.int32)
        item[: self.item_value.shape[0]] = self.item_value
        key = np.full(qb, PAD_KEY_VALUE, dtype=np.int32)
        key[: self.key_value.shape[0]] = self.key_value
        return item, key


def _count_upload(elig_ids, *operands) -> None:
    """Counters of the open ``filter.launch`` span: the padded operands sent
    to the device (``h2d_bytes``) and the eligibility operands alone
    (``elig_bytes``: the two padded id vectors)."""
    elig_bytes = 0 if elig_ids is None else sum(int(x.nbytes) for x in elig_ids)
    telemetry.count("h2d_bytes", elig_bytes + sum(int(x.nbytes) for x in operands))
    telemetry.count("elig_bytes", elig_bytes)


def filter_match_auto(
    row_sk: np.ndarray | jnp.ndarray,
    query_sk: np.ndarray | jnp.ndarray,
    backend: Backend | str | None = None,
) -> np.ndarray:
    """Backend-dispatched super-key row filter (§6.3): bool[n, q] on the host.

    On TPU this launches the Pallas ``filter_kernel`` (the memory-roofline
    path); on any other backend (CPU/GPU hosts) it runs the vectorised XLA
    subsumption instead of the Pallas interpreter, which is orders of
    magnitude slower per launch.  Tiny blocks (< ~100k probes) short-circuit
    to numpy, where the XLA dispatch latency alone would dominate.
    ``backend`` pins one path (resolved via ``kernels.registry``: explicit >
    ``MATE_FILTER_BACKEND`` > platform default — the CI matrix uses the env
    level to exercise interpret-mode Pallas on CPU hosts).
    """
    n, q = row_sk.shape[0], query_sk.shape[0]
    if n == 0 or q == 0:
        return np.zeros((n, q), dtype=bool)
    backend = registry.resolve_backend(backend).name
    if backend in ("fused", "fused-gather"):
        backend = "pallas"  # fused paths have no matrix output; same family
    if backend == "auto":
        backend = "numpy" if n * q < _MIN_XLA_PROBES else "xla"
    if backend == "numpy":
        return subsume_np(row_sk, query_sk)
    if backend == "xla":
        rows = _pad_to(
            jnp.asarray(row_sk, jnp.uint32), 0, _pow2_bucket(n, _FALLBACK_MIN_N)
        )
        qry = _pad_to(
            jnp.asarray(query_sk, jnp.uint32), 0, _pow2_bucket(q, _FALLBACK_MIN_Q)
        )
        return np.asarray(_subsume_block(rows, qry))[:n, :q]
    return np.asarray(filter_match(row_sk, query_sk))


def _per_table_counts(hits, seg, num_segments: int):
    """Per-table eligible-hit counts from a bool[n, q] hits matrix.

    The row reduction runs as an f32 matvec — on CPU XLA that lowers to a
    BLAS gemv and is ~1.6x faster end-to-end than an integer row sum, which
    forces a second un-fused pass over the matrix.  f32 is exact here
    (row sums are bounded by q « 2^24).
    """
    ones = jnp.ones((hits.shape[1], 1), jnp.float32)
    per_row = (hits.astype(jnp.float32) @ ones)[:, 0].astype(jnp.int32)
    return jax.ops.segment_sum(per_row, seg, num_segments=num_segments)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _hits_counts_block(
    row_sk, query_sk, item_value, key_value, seg, *, num_segments: int
):
    """Subsumption ∧ eligibility plus per-table hit counts, all on device;
    the eligibility mask is formed here from the two id vectors."""
    hits = jnp.all((query_sk[None, :, :] & ~row_sk[:, None, :]) == 0, axis=-1)
    hits = hits & (item_value[:, None] == key_value[None, :])
    return hits, _per_table_counts(hits, seg, num_segments)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _combine_counts(match, item_value, key_value, seg, *, num_segments: int):
    """Same reduction as ``_hits_counts_block`` over a precomputed match."""
    hits = match.astype(jnp.bool_) & (item_value[:, None] == key_value[None, :])
    return hits, _per_table_counts(hits, seg, num_segments)


# above this table count the fused one-hot tile would blow VMEM even at the
# minimum row block (see filter_kernel.fused_block_n): wider launches split
# into table_chunks
_FUSED_MAX_TABLES = filter_kernel.FUSED_MAX_TABLES


def filter_table_counts(
    row_sk: np.ndarray | jnp.ndarray,
    query_sk: np.ndarray | jnp.ndarray,
    elig: Eligibility | None,
    seg_ids: np.ndarray,
    n_tables: int,
    *,
    mode: str = "sum",
    interpret: bool | None = None,
    block_n: int | None = None,
) -> np.ndarray:
    """Fused filter+segment-count launch: per-table eligible-hit counts with
    COUNTS-ONLY readback — the rows × queries match matrix is never
    materialised, not even in HBM (paper §6.3 at its true roofline:
    ~16 bytes read per row, 4 bytes written per table).

    Args:
      row_sk:   uint32[n, lanes] candidate-row super keys.
      query_sk: uint32[q, lanes] query-key super keys.
      elig:     per-item and per-key init-value ids (``Eligibility``), or
                None (all eligible).
      seg_ids:  int32[n] table index (0..n_tables) of each candidate item.
      n_tables: number of tables covered by this block.
      mode:     'sum' (eligible hits per table) | 'any' (rows with ≥1 hit).
      block_n:  optional power-of-two row-block override
                (``DiscoveryConfig.fused_block_n``); clamped to the VMEM
                budget block, so it can only shrink the tile, never blow it.
    Returns:
      int32[n_tables] counts on the host — the only transfer.
    """
    n, q = row_sk.shape[0], query_sk.shape[0]
    if n == 0 or q == 0 or n_tables == 0:
        return np.zeros(n_tables, dtype=np.int32)
    assert n_tables <= _FUSED_MAX_TABLES, n_tables
    interpret = _on_cpu() if interpret is None else interpret
    nb = _bucket(n, _FALLBACK_MIN_N)
    qb = _pow2_bucket(q, _FALLBACK_MIN_Q)
    tb = max(-(-n_tables // 128) * 128, 128)
    # power-of-two block ≤ nb: divides both pow2 buckets and 8192-multiples,
    # so the grid covers every padded row exactly
    budget_n = filter_kernel.fused_block_n(tb)
    if block_n is not None:
        _check_fused_block_n(block_n)
        budget_n = min(budget_n, block_n)
    block_n = min(nb, budget_n)
    block_q = qb if mode == "any" else min(qb, filter_kernel.DEFAULT_BLOCK_Q)
    with telemetry.span("filter.pad"):
        rows_p = np.zeros((nb, row_sk.shape[1]), dtype=np.uint32)
        rows_p[:n] = row_sk
        # padded queries get all-ones super keys (subsumed by nothing)
        qry_p = np.full((qb, query_sk.shape[1]), 0xFFFFFFFF, dtype=np.uint32)
        qry_p[:q] = query_sk
        seg_p = np.full(nb, -1, dtype=np.int32)  # padding rows scatter nowhere
        seg_p[:n] = seg_ids
        elig_ids = None if elig is None else elig.padded(nb, qb)
    with telemetry.span("filter.upload"):
        counts, _key_counts = filter_kernel.filter_table_counts(
            jnp.asarray(rows_p).T,
            jnp.asarray(qry_p).T,
            None if elig_ids is None else tuple(map(jnp.asarray, elig_ids)),
            jnp.asarray(seg_p),
            n_tables=tb,
            n_queries=q,
            block_n=block_n,
            block_q=block_q,
            mode=mode,
            interpret=interpret,
        )
    _count_upload(elig_ids, rows_p, qry_p, seg_p)
    with telemetry.span("filter.readback"):
        return np.asarray(counts)[:n_tables]


# device superkey stores above this size stay host-resident and the
# fused-gather backend demotes to the host-gather fused launch — a lake that
# big should be sharded across hosts (ROADMAP item 1) rather than squeezed
# into one device's HBM alongside the working set.
GATHER_STORE_MAX_BYTES = 2 << 30


def gather_store_fits(superkeys: np.ndarray | jnp.ndarray) -> bool:
    """True when the per-row superkey store fits the device-store budget."""
    return superkeys.nbytes <= GATHER_STORE_MAX_BYTES


class DeviceStore(NamedTuple):
    """A device-resident superkey store in the gather kernel's packed layout.

    ``lines`` is the row-major ``uint32[n_rows, lanes]`` superkey array
    reshaped to ``uint32[n_lines, 128]`` (zero-padded to whole (8, 128)
    tiles): its HBM footprint is the logical ``lanes × 4`` bytes per row.
    A ``[n_rows, lanes]`` operand would instead be relaid out lane-padded
    to 128 lanes for the kernel (32× the logical bytes at 128 bits)."""

    lines: jax.Array  # uint32[n_lines, 128]
    lanes: int  # uint32 lanes per superkey row
    n_rows: int


def device_store(superkeys: np.ndarray, device=None) -> DeviceStore:
    """Pack ``uint32[n_rows, lanes]`` superkeys into a ``DeviceStore`` and
    upload it (to ``device`` when given, else the default device)."""
    superkeys = np.asarray(superkeys, dtype=np.uint32)
    n, lanes = superkeys.shape
    line = filter_kernel.STORE_LINE
    n_lines = max(-(-(n * lanes) // (8 * line)) * 8, 8)
    flat = np.zeros(n_lines * line, dtype=np.uint32)
    flat[: n * lanes] = superkeys.reshape(-1)
    return DeviceStore(
        jax.device_put(flat.reshape(n_lines, line), device), lanes, n
    )


def table_chunks(seg_ids: np.ndarray, n_tables: int):
    """Split one fused launch into launches of at most ``_FUSED_MAX_TABLES``
    tables (the scatter tile's VMEM cap): yields ``(item slice, table lo,
    table hi)``.  Every engine emits ascending seg ids (CSR table order), so
    each table range is one contiguous item range."""
    seg = np.asarray(seg_ids)
    if n_tables <= _FUSED_MAX_TABLES:
        yield slice(0, seg.shape[0]), 0, n_tables
        return
    if np.any(np.diff(seg) < 0):
        raise ValueError("seg_ids must be ascending to split a launch by table")
    for t_lo in range(0, n_tables, _FUSED_MAX_TABLES):
        t_hi = min(t_lo + _FUSED_MAX_TABLES, n_tables)
        i_lo, i_hi = np.searchsorted(seg, [t_lo, t_hi])
        yield slice(int(i_lo), int(i_hi)), t_lo, t_hi


def chunked_counts(launch, seg_ids, elig, n_tables: int, *per_item):
    """Run ``launch(*per_item_slices, elig_slice, seg_slice, n)`` once per
    ``table_chunks`` range and concatenate the per-table counts; the
    ``Eligibility`` is sliced with the items."""
    counts = np.zeros(n_tables, dtype=np.int32)
    seg = np.asarray(seg_ids)
    for sl, t_lo, t_hi in table_chunks(seg, n_tables):
        counts[t_lo:t_hi] = launch(
            *(x[sl] for x in per_item),
            None if elig is None else elig[sl],
            seg[sl] - t_lo,
            t_hi - t_lo,
        )
    return counts


def gather_filter_table_counts(
    store: DeviceStore,
    rows: np.ndarray,
    query_sk: np.ndarray | jnp.ndarray,
    elig: Eligibility | None,
    seg_ids: np.ndarray,
    n_tables: int,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """Gather-fused filter+segment-count launch: posting-list row offsets in,
    per-table counts out — ONE launch from CSR posting lists to counts.

    The composed path ships n×lanes gathered superkeys through HBM before the
    filter ever runs; here the kernel takes the (ragged, padded) row offsets
    and DMA-gathers each row block from the device-resident ``store``
    straight into VMEM, so the gathered block never exists in HBM and the
    host ships n×4 offset bytes instead of n×lanes×4 key bytes.

    Args:
      store:    packed device-resident superkey store (``device_store``,
                ``MateIndex.device_store()``).
      rows:     int[n] row offsets into ``store`` (the CSR candidate rows).
      query_sk: uint32[q, lanes] query-key super keys; ``lanes <=
                store.lanes`` probes a lane-prefix degrade over the
                full-width store.
      elig:     per-item and per-key init-value ids (``Eligibility``), or
                None (all eligible).
      seg_ids:  int32[n] table index (0..n_tables) of each candidate item.
      n_tables: number of tables covered by this block.
      block_n:  optional power-of-two row-block override
                (``DiscoveryConfig.fused_block_n``); clamped to the VMEM
                budget block, so it can only shrink the tile, never blow it.
    Returns:
      int32[n_tables] counts on the host — bit-identical to
      ``filter_table_counts(superkeys[rows][:, :lanes], ...)`` (mode='sum').
    """
    n, q = rows.shape[0], query_sk.shape[0]
    if n == 0 or q == 0 or n_tables == 0:
        return np.zeros(n_tables, dtype=np.int32)
    if n_tables > _FUSED_MAX_TABLES:
        raise ValueError(
            f"gather-fused scatter tile supports at most {_FUSED_MAX_TABLES}"
            f" tables per launch, got {n_tables} — split the launch with"
            " table_chunks"
        )
    interpret = _on_cpu() if interpret is None else interpret
    nb = _bucket(n, _FALLBACK_MIN_N)
    qb = _pow2_bucket(q, _FALLBACK_MIN_Q)
    tb = max(-(-n_tables // 128) * 128, 128)
    budget_n = filter_kernel.fused_block_n(tb)
    if block_n is not None:
        _check_fused_block_n(block_n)
        budget_n = min(budget_n, block_n)
    block_n = min(nb, budget_n)
    block_q = min(qb, filter_kernel.DEFAULT_BLOCK_Q)
    with telemetry.span("filter.pad"):
        # padding offsets point at row 0 (always valid); their seg id is -1
        # so they scatter nowhere regardless of what row 0's superkey matches.
        rows_p = np.zeros(nb, dtype=np.int32)
        rows_p[:n] = rows
        qry_p = np.full((qb, query_sk.shape[1]), 0xFFFFFFFF, dtype=np.uint32)
        qry_p[:q] = query_sk
        seg_p = np.full(nb, -1, dtype=np.int32)
        seg_p[:n] = seg_ids
        elig_ids = None if elig is None else elig.padded(nb, qb)
    with telemetry.span("filter.upload"):
        counts = filter_kernel.gather_filter_table_counts(
            jnp.asarray(rows_p),
            store.lines,
            jnp.asarray(qry_p).T,
            None if elig_ids is None else tuple(map(jnp.asarray, elig_ids)),
            jnp.asarray(seg_p),
            store_lanes=store.lanes,
            n_tables=tb,
            n_queries=q,
            block_n=block_n,
            block_q=block_q,
            interpret=interpret,
        )
    _count_upload(elig_ids, rows_p, qry_p, seg_p)
    with telemetry.span("filter.readback"):
        return np.asarray(counts)[:n_tables]


def filter_hits_table_counts(
    row_sk: np.ndarray | jnp.ndarray,
    query_sk: np.ndarray | jnp.ndarray,
    elig: Eligibility,
    seg_ids: np.ndarray,
    n_tables: int,
    *,
    use_device: bool = True,
    backend: Backend | str | None = None,
    fused_block_n: int | None = None,
    store: DeviceStore | None = None,
    rows: np.ndarray | None = None,
) -> tuple[np.ndarray | jnp.ndarray | None, np.ndarray]:
    """Device-side inputs for the §6.2 bound checks: eligible filter hits plus
    per-table hit counts, WITHOUT transferring the match matrix to the host.

    Args:
      row_sk:   uint32[n, lanes] candidate-row super keys.
      query_sk: uint32[q, lanes] query-key super keys.
      elig:     init-value eligibility per (item, key) pair, as per-item and
                per-key ids (``Eligibility``); a launch uploads only the ids.
      seg_ids:  int32[n] table index (0..n_tables) of each candidate item.
      n_tables: number of tables covered by this block.
      use_device: False forces the host numpy path (legacy ``use_kernel``).
      backend:  resolved ``Backend`` (or name) for this call; None follows
                the registry precedence (env var, then platform default).
      fused_block_n: optional row-block override for the fused launch.
      store:    device-resident superkey store for the ``fused-gather``
                backend (``MateIndex.device_store()``); with ``rows`` set the
                gather-fused launch replaces ``row_sk`` entirely.  Without
                it ``fused-gather`` runs the host-gather fused launch.
      rows:     int[n] store row offsets for the gather-fused launch.
    Returns:
      (hits, counts) — ``counts`` int32[n_tables] is the one per-launch host
      readback the rule-1/rule-2 bounds consume.  On the composed XLA/Pallas
      paths ``hits`` bool[n, q] stays device-resident (slice it per surviving
      table; only those slices are ever read back).  On the FUSED paths
      ``hits`` is None: the match matrix was never produced at all — callers
      recompute the (few) surviving tables' slices on demand.  ``row_sk`` may
      be None when ``store``+``rows`` are given (the gather-fused contract:
      the host never gathers the candidate superkeys).  Fused launches over
      more tables than the scatter tile holds split into ``table_chunks``.
    """
    with telemetry.span("filter.launch"):
        n = rows.shape[0] if row_sk is None else row_sk.shape[0]
        q = query_sk.shape[0]
        if n == 0 or q == 0 or n_tables == 0:
            return np.zeros((n, q), dtype=bool), np.zeros(n_tables, dtype=np.int32)
        if not use_device:
            backend = "numpy"
        backend = registry.resolve_backend(backend).name
        if backend == "fused-gather" and store is not None:
            counts = chunked_counts(
                lambda r, e, s, nt: gather_filter_table_counts(
                    store, r, query_sk, e, s, nt, block_n=fused_block_n
                ),
                seg_ids, elig, n_tables, np.asarray(rows),
            )
            return None, counts
        if backend in ("fused", "fused-gather"):
            # fused-gather without a store: the caller kept the store off the
            # device (over budget) and counts that demotion
            counts = chunked_counts(
                lambda r, e, s, nt: filter_table_counts(
                    r, query_sk, e, s, nt, block_n=fused_block_n
                ),
                seg_ids, elig, n_tables, np.asarray(row_sk),
            )
            return None, counts
        if backend == "auto":
            backend = "numpy" if n * q < _MIN_XLA_PROBES else "xla"
        if backend == "numpy":
            hits = subsume_np(row_sk, query_sk) & elig.dense()
            counts = np.bincount(
                np.asarray(seg_ids, dtype=np.int64),
                weights=hits.sum(axis=1),
                minlength=n_tables,
            ).astype(np.int32)
            return hits, counts[:n_tables]
        # bucket every dim so XLA compiles O(few) distinct shapes; padded
        # rows/queries carry padding ids eligible with nothing, so their
        # (arbitrary) super keys and the segment-0 padding of seg_ids
        # contribute nothing to hits or counts.
        nb = _bucket(n, _FALLBACK_MIN_N)
        qb = _pow2_bucket(q, _FALLBACK_MIN_Q)
        tb = _pow2_bucket(n_tables, 16)
        with telemetry.span("filter.pad"):
            rows_p = np.zeros((nb, row_sk.shape[1]), dtype=np.uint32)
            rows_p[:n] = row_sk
            qry_p = np.zeros((qb, query_sk.shape[1]), dtype=np.uint32)
            qry_p[:q] = query_sk
            elig_ids = elig.padded(nb, qb)
            seg_p = np.zeros(nb, dtype=np.int32)
            seg_p[:n] = seg_ids
        with telemetry.span("filter.upload"):
            if backend == "pallas":
                interpret = _on_cpu()
                match = filter_kernel.filter_match(
                    jnp.asarray(rows_p).T,
                    jnp.asarray(qry_p).T,
                    block_n=min(nb, filter_kernel.DEFAULT_BLOCK_N),
                    block_q=min(qb, filter_kernel.DEFAULT_BLOCK_Q),
                    interpret=interpret,
                )
                hits, counts = _combine_counts(
                    match, *map(jnp.asarray, elig_ids), jnp.asarray(seg_p),
                    num_segments=tb,
                )
            else:
                hits, counts = _hits_counts_block(
                    jnp.asarray(rows_p),
                    jnp.asarray(qry_p),
                    *map(jnp.asarray, elig_ids),
                    jnp.asarray(seg_p),
                    num_segments=tb,
                )
        _count_upload(elig_ids, rows_p, qry_p, seg_p)
        with telemetry.span("filter.readback"):
            counts = np.asarray(counts)[:n_tables]
        return hits[:n, :q], counts


def filter_count(
    row_sk: jnp.ndarray,
    query_sk: jnp.ndarray,
    *,
    block_n: int | None = None,
    block_q: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused per-query candidate count: -> int32[q].

    Padded rows must NOT count: they are padded with all-zero super keys and
    an all-zero query would wrongly match them, so the wrapper pads queries
    with all-ones (matching nothing except all-ones rows, which padding never
    creates) and subtracts nothing for rows: a zero row superkey subsumes only
    zero queries — real queries always have ≥1 bit per non-empty key value, so
    zero-key queries (empty strings) are the only edge case and they match
    every row under ANY filter (vacuous truth), identical to the reference.
    """
    interpret = _on_cpu() if interpret is None else interpret
    block_n = block_n or filter_kernel.DEFAULT_BLOCK_N
    block_q = block_q or filter_kernel.DEFAULT_BLOCK_Q
    n, q = row_sk.shape[0], query_sk.shape[0]
    row_t = _pad_to(jnp.asarray(row_sk, jnp.uint32).T, 1, block_n, value=0)
    qry_t = _pad_to(
        jnp.asarray(query_sk, jnp.uint32).T, 1, block_q, value=np.uint32(0xFFFFFFFF)
    )
    counts = filter_kernel.filter_count(
        row_t, qry_t, block_n=block_n, block_q=block_q, interpret=interpret
    )
    # padded rows have zero super keys: they match a query only if the query
    # is all-zero; correct for that exact case.
    n_pad = row_t.shape[1] - n
    if n_pad:
        zero_q = jnp.all(jnp.asarray(query_sk, jnp.uint32) == 0, axis=-1)
        counts = counts[:q] - jnp.where(zero_q, n_pad, 0).astype(jnp.int32)
        return counts
    return counts[:q]
