"""Kernel microbenchmarks (interpret-mode wall clock on CPU is NOT a TPU
number — the derived column carries the structural throughput metrics that
transfer: bytes/row touched, probes per byte; see EXPERIMENTS.md §Roofline
for the device-level analysis) + batched-vs-sequential engine comparison."""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import common
from repro.core import discovery, xash
from repro.core.batched import discover_many
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _time(fn, *args, n=3, **kw):
    fn(*args, **kw)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / n


def kernels():
    print("# kernel microbench (interpret mode)")
    cfg = xash.DEFAULT_CONFIG
    enc = RNG.integers(0, 38, size=(4096, 6, 48)).astype(np.uint8)
    dt = _time(ops.superkey, enc, cfg)
    common.emit(
        "kern/xash_superkey_4096x6", dt * 1e6,
        f"rows_per_s={4096/dt:,.0f};bytes_per_row={6*48+16}"
    )
    row_sk = np.asarray(ref.xash_superkey_ref(enc, cfg))
    q_sk = row_sk[:256]
    dt = _time(ops.filter_count, row_sk, q_sk)
    probes = row_sk.shape[0] * q_sk.shape[0]
    common.emit(
        "kern/filter_count_4096x256", dt * 1e6,
        f"probes_per_s={probes/dt:,.0f};bytes_per_probe={2*16/256:.3f}"
    )
    dt_ref = _time(
        lambda: np.asarray(ref.filter_count_ref(row_sk, q_sk))
    )
    common.emit(
        "kern/filter_count_jnp_ref", dt_ref * 1e6,
        f"kernel_vs_ref={dt_ref/dt:.2f}x"
    )
    # backend-dispatched filter the online engine actually calls (Pallas on
    # TPU, vectorised XLA on CPU) — the per-launch cost the batched engine
    # amortises over whole table batches
    dt_auto = _time(ops.filter_match_auto, row_sk, q_sk)
    backend = jax.default_backend()
    dispatch = "pallas" if backend == "tpu" else "xla"
    common.emit(
        "kern/filter_match_auto_4096x256", dt_auto * 1e6,
        f"probes_per_s={probes/dt_auto:,.0f};backend_dispatch={backend}_{dispatch}"
    )
    # fused filter+segment-count vs the composed path (match matrix + XLA
    # segment-sum): identical probes and counts, but the fused launch's only
    # outputs are the two counts vectors — the n×q int8 match matrix (the
    # dominant write of the composed path) never exists, which is the
    # structural bytes-moved metric that transfers to TPU (see
    # docs/BENCHMARKS.md §Roofline).
    n, q = row_sk.shape[0], q_sk.shape[0]
    n_tables = 64
    seg = np.sort(RNG.integers(0, n_tables, n)).astype(np.int32)
    elig = ops.Eligibility(np.zeros(n, np.int32), np.zeros(q, np.int32))  # all
    dt_fused = _time(ops.filter_table_counts, row_sk, q_sk, elig, seg, n_tables)
    dt_comp = _time(
        lambda: ops.filter_hits_table_counts(
            row_sk, q_sk, elig, seg, n_tables, backend="xla"
        )[1]
    )
    out_fused = 4 * n_tables + 4 * q  # counts + key-counts vectors
    out_comp = n * q + 4 * n_tables  # int8 match matrix + counts
    common.emit(
        "kern/filter_table_counts_fused_4096x256", dt_fused * 1e6,
        f"out_bytes={out_fused};matrix_bytes_avoided={n*q};"
        f"bytes_out_vs_composed={out_fused/out_comp:.4f}",
        backend="fused",  # this row pins the fused kernel regardless of env
    )
    common.emit(
        "kern/filter_table_counts_composed_4096x256", dt_comp * 1e6,
        f"out_bytes={out_comp};fused_vs_composed_wallclock={dt_comp/dt_fused:.2f}x",
        backend="xla",  # composed reference is pinned to the XLA path
    )
    # gather-fused: same launch, but candidate INPUT rows are DMA-gathered
    # from the device-resident superkey store inside the kernel — the host
    # ships n int32 offsets instead of n×lanes uint32 superkeys.  The
    # structural metric is input bytes shipped per launch; wall clock in
    # interpret mode only shows the path isn't pathological.
    store = ops.device_store(
        np.concatenate([row_sk, RNG.integers(0, 2**32, row_sk.shape, np.uint32)])
    )
    rows_idx = RNG.permutation(store.n_rows)[:n].astype(np.int64)
    dt_gather = _time(
        ops.gather_filter_table_counts, store, rows_idx, q_sk, elig, seg, n_tables
    )
    lanes = row_sk.shape[1]
    in_gather = n * 4  # int32 offsets
    in_comp = n * lanes * 4  # host-gathered uint32 superkeys
    common.emit(
        "kern/gather_filter_table_counts_4096x256", dt_gather * 1e6,
        f"in_bytes={in_gather};gather_bytes_saved={in_comp - in_gather};"
        f"in_bytes_vs_composed={in_gather/in_comp:.4f};"
        f"gather_vs_fused_wallclock={dt_gather/dt_fused:.2f}x",
        backend="fused-gather",  # this row pins the gather-fused kernel
    )


def engines():
    print("# engine comparison: SCI vs MATE(seq) vs MATE(batched/fused)")
    queries = common.query_group(common.ROWS["webtable(100)"])
    idx = common.index("xash", 128)
    # warm jit/dispatch caches so the timed runs (and the CI regression gate
    # ratios derived from them) measure steady state, not compiles
    for engine in ("seq", "batched", "batched_fused", "batched_gather"):
        common.run_discovery(idx, queries, engine=engine)
    t_sci, _ = common.run_discovery(idx, queries, row_filter=False)
    t_seq, _ = common.run_discovery(idx, queries)
    t_bat, stb = common.run_discovery(idx, queries, engine="batched")
    t_fus, stf = common.run_discovery(idx, queries, engine="batched_fused")
    n = len(queries)
    common.emit("engine/sci", t_sci / n * 1e6, "row_filter=off")
    common.emit("engine/mate_seq", t_seq / n * 1e6, f"vs_sci={t_sci/t_seq:.2f}x")
    common.emit(
        "engine/mate_batched", t_bat / n * 1e6,
        f"vs_sci={t_sci/t_bat:.2f}x;vs_seq={t_seq/t_bat:.2f}x"
    )
    # fused filter+segment-count engine path: the structural claim the gate
    # checks is matrix_bytes == 0 (counts-only readback); wall-clock vs the
    # composed engine only transfers on TPU backends.
    common.emit(
        "engine/mate_batched_fused", t_fus / n * 1e6,
        f"vs_seq={t_seq/t_fus:.2f}x;matrix_bytes={stf['matrix_bytes']};"
        f"fused_launches={stf['fused_launches']};"
        f"readback_bytes={stf['readback_bytes']}",
        backend="fused",  # run_discovery pins backend='fused' for this row
    )
    # gather-fused engine path: same counts-only contract PLUS no host
    # superkey gather — gather_saved counts the launch input bytes that
    # stayed in the device store (n_candidates × (lanes·4 − 4) per launch).
    t_gat, stg = common.run_discovery(idx, queries, engine="batched_gather")
    common.emit(
        "engine/mate_batched_gather", t_gat / n * 1e6,
        f"vs_fused={t_fus/t_gat:.2f}x;matrix_bytes={stg['matrix_bytes']};"
        f"fused_launches={stg['fused_launches']};"
        f"gather_bytes_saved={stg['gather_saved']}",
        backend="fused-gather",  # run_discovery pins backend='fused-gather'
    )
    # routed lake (4 shards): shard-local launches + count-only merge.  The
    # structural claims the gate checks: bit-identical top-k to the
    # single-host engine, and the ONLY cross-shard traffic is the int32
    # count vectors — route_bytes ≪ the superkey bytes a host-gather ships.
    ridx = common.routed_index(4, 128)
    common.run_discovery(ridx, queries, engine="batched")  # warm
    identical = int(
        all(
            [(e.table_id, e.joinability) for e in discover_many(
                ridx, [(q, c)], k=common.K)[0][0]]
            == [(e.table_id, e.joinability) for e in discover_many(
                idx, [(q, c)], k=common.K)[0][0]]
            for q, c in queries
        )
    )
    t_rt, strt = common.run_discovery(ridx, queries, engine="batched")
    host_gather_bytes = strt["items_checked"] * ridx.cfg.lanes * 4
    common.emit(
        "engine/mate_batched_routed", t_rt / n * 1e6,
        f"vs_batched={t_bat/t_rt:.2f}x;identical={identical};"
        f"shard_launches={strt['shard_launches']};"
        f"route_bytes_merged={strt['route_bytes']};"
        f"route_frac={strt['route_bytes']/max(host_gather_bytes,1):.4f}",
    )


def main():
    kernels()
    engines()
    common.save_trajectory("kernels")


if __name__ == "__main__":
    main()
