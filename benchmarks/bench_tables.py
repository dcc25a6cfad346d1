"""Paper Table 1 (runtime) + Table 2 (precision) analogs, plus the
offline-phase build-time section (``index_build`` trajectory).

Runtime of top-k n-ary discovery per hash function / hash size, and
macro-averaged precision (mean ± std over queries), on the synthetic lake
calibrated to webtable statistics (power-law widths, ~12 PL items/value).

``--only index_build`` runs just the build section (what CI's bench job
gates through ``tools/check_bench.py``): single-host build time with
structural metrics (values/bytes hashed are seed-deterministic, gated
exactly) and a host-sharded build asserting byte-identity to the
single-host artifacts, with the merge-cost fraction gated so the shard
merge can never quietly grow superlinear.
"""

from __future__ import annotations

import argparse
import time

from benchmarks import common


HASHES_128 = ["md5", "murmur", "city", "simhash", "ht", "bf", "xash"]
HASHES_512 = ["simhash", "ht", "bf", "xash"]
# gates the 512-bit engine row in table_engines (run.py --quick clears it
# together with HASHES_512 to skip all 512-bit index builds)
ENGINE_512 = True


def table1_runtime():
    print("# Table 1 analog: discovery runtime (SCI baseline + hash variants)")
    out = {}
    for gname, n_rows in common.ROWS.items():
        queries = common.query_group(n_rows)
        idx_x = common.index("xash", 128)
        dt, st = common.run_discovery(idx_x, queries, row_filter=False)
        out[(gname, "sci", 128)] = (dt, st)
        common.emit(
            f"t1/{gname}/sci", dt / len(queries) * 1e6,
            f"precision={st['precision_mean']:.3f}"
        )
        for bits, hashes in ((128, HASHES_128), (512, HASHES_512)):
            for h in hashes:
                idx = common.index(h, bits)
                dt, st = common.run_discovery(idx, queries)
                out[(gname, h, bits)] = (dt, st)
                common.emit(
                    f"t1/{gname}/{h}({bits})", dt / len(queries) * 1e6,
                    f"precision={st['precision_mean']:.3f};fp={st['fp']}"
                )
        # headline ratios (paper: MATE up to 20x over SCI; XASH ≤2.2x over BF)
        sci_t = out[(gname, "sci", 128)][0]
        x_t = out[(gname, "xash", 128)][0]
        bf_t = out[(gname, "bf", 128)][0]
        common.emit(
            f"t1/{gname}/speedups", 0.0,
            f"mate_vs_sci={sci_t/x_t:.2f}x;xash_vs_bf={bf_t/x_t:.2f}x"
        )
    return out


def table_engines():
    """Beyond-paper §6.3 fast path: scalar Alg. 1 vs batched kernel-backed
    engine vs multi-query shared-launch batching (docs/ARCHITECTURE.md ADR)."""
    print("# Engine comparison: scalar vs batched (kernel) vs multi-query")
    out = {}
    for gname, n_rows in common.ROWS.items():
        queries = common.query_group(n_rows)
        idx = common.index("xash", 128)
        # warm up jit caches (full group: the multi-query launch shape
        # depends on the whole group) so we measure steady-state serving
        for engine in ("seq", "batched", "many"):
            common.run_discovery(idx, queries, engine=engine)
        times = {}
        for engine in ("seq", "batched", "batched_np", "many"):
            dt, st = common.run_discovery(idx, queries, engine=engine)
            times[engine] = dt
            out[(gname, engine)] = (dt, st)
            # launch transfer behaviour: fraction of the match matrix
            # materialised on the host — all of it on the non-fused
            # backends, which read it back in one transfer.  Undefined for the
            # scalar engine (no match matrix), so only batched/many rows
            # carry the field.
            rb = ""
            if st["matrix_bytes"]:
                rb = (
                    f";match_readback_frac="
                    f"{st['readback_bytes'] / st['matrix_bytes']:.3f}"
                )
            common.emit(
                f"engines/{gname}/{engine}", dt / len(queries) * 1e6,
                f"precision={st['precision_mean']:.3f};passed={st['passed']}{rb}",
                # batched_np pins the numpy oracle in code; the others follow
                # the process-level registry resolution
                backend="numpy" if engine == "batched_np" else None,
            )
        common.emit(
            f"engines/{gname}/speedups", 0.0,
            f"batched_vs_seq={times['seq']/times['batched']:.2f}x;"
            f"many_vs_seq={times['seq']/times['many']:.2f}x"
        )
        # 512-bit end-to-end engine path (16 lanes through the same kernels)
        if ENGINE_512:
            idx512 = common.index("xash", 512)
            common.run_discovery(idx512, queries, engine="batched")  # warm jit
            dt, st = common.run_discovery(idx512, queries, engine="batched")
            rb = st["readback_bytes"] / max(st["matrix_bytes"], 1)
            common.emit(
                f"engines/{gname}/batched(512)", dt / len(queries) * 1e6,
                f"precision={st['precision_mean']:.3f};passed={st['passed']};"
                f"match_readback_frac={rb:.3f};vs_128={times['batched']/dt:.2f}x"
            )
    return out


def index_build():
    """Offline phase (§4/§5) build-time rows — the ``index_build`` section.

    The sharded row uses the host-sharded path (4 shards, no device mesh):
    the same hash work plus the shard-merge bookkeeping, so
    ``sharded_vs_single`` isolates the merge/bookkeeping overhead and
    ``identical`` pins the byte-identity contract on every bench run.
    """
    from repro.core import xash
    from repro.core.index import build_index, index_artifacts_equal

    print("# index_build: offline-phase build time (single-host vs sharded merge)")
    c = common.corpus()
    cfg = xash.XashConfig(
        bits=128, char_freq=tuple(c.char_frequencies().tolist())
    )
    # warm the jit caches of both paths so the rows measure steady-state
    # hashing, not compile time (shard shapes differ from the single pass)
    build_index(c, cfg=cfg)
    build_index(c, cfg=cfg, n_shards=4)

    t0 = time.perf_counter()
    ref, st1 = build_index(c, cfg=cfg)
    dt_single = time.perf_counter() - t0
    common.emit(
        "build/xash(128)", dt_single * 1e6,
        f"values={st1.values_total};bytes_hashed={st1.bytes_hashed};"
        f"rows={st1.rows_total};"
        f"hash_frac={st1.hash_seconds / max(st1.total_seconds, 1e-9):.3f}",
    )
    t0 = time.perf_counter()
    idx4, st4 = build_index(c, cfg=cfg, n_shards=4)
    dt_sharded = time.perf_counter() - t0
    identical = index_artifacts_equal(idx4, ref)
    common.emit(
        "build/sharded_host(4)", dt_sharded * 1e6,
        f"identical={identical};"
        f"sharded_vs_single={dt_sharded / max(dt_single, 1e-9):.2f}x;"
        f"merge_frac={st4.merge_seconds / max(st4.total_seconds, 1e-9):.4f};"
        f"shards={st4.n_shards}",
    )
    if ENGINE_512:
        cfg512 = xash.XashConfig(
            bits=512, char_freq=tuple(c.char_frequencies().tolist())
        )
        build_index(c, cfg=cfg512)  # warm
        t0 = time.perf_counter()
        _idx, st = build_index(c, cfg=cfg512)
        dt = time.perf_counter() - t0
        common.emit(
            "build/xash(512)", dt * 1e6,
            f"values={st.values_total};bytes_hashed={st.bytes_hashed};"
            f"vs_128={dt / max(dt_single, 1e-9):.2f}x",
        )


N_DISTINCT = 24  # distinct query tables behind the Zipf traffic
N_REQUESTS = 160
ZIPF_S = 1.1  # skew exponent: rank-r query drawn with p ∝ 1/r^s


def serving():
    """Online serving tier under skewed traffic — the ``serving`` section.

    Zipf-distributed requests (a few hot query tables dominate, FREYJA-style)
    flow through ``serve.engine.DiscoveryEngine`` with both serving caches
    enabled.  Everything gated is seed-deterministic: the traffic, hence the
    result-cache hit count, hence the bound-cache replay count; latency
    percentiles are emitted for the trajectory but NOT gated (machine noise).
    ``bit_identical`` pins the serving tier's core contract on every bench
    run: cached answers are indistinguishable from a cold ``discover``.
    """
    import numpy as np

    from repro.core.batched import discover_many
    from repro.core.session import DiscoveryConfig, MateSession
    from repro.data import synthetic
    from repro.serve.engine import DiscoveryEngine

    print("# serving: Zipf traffic through the cached DiscoveryEngine")
    idx = common.index("xash", 128)
    distinct = synthetic.make_mixed_queries(
        common.corpus(), N_DISTINCT, 10, 2, seed=common.SEED + 9
    )
    ranks = np.arange(1, N_DISTINCT + 1, dtype=np.float64)
    probs = ranks**-ZIPF_S
    probs /= probs.sum()
    rng = np.random.default_rng(common.SEED + 11)
    traffic = rng.choice(N_DISTINCT, size=N_REQUESTS, p=probs)

    # steady state: warm the filter path's compile caches outside the engine
    common.run_discovery(idx, distinct, engine="many")
    # cold ground truth per distinct query (computed outside the timed loop)
    def key(entries):
        return [(e.table_id, e.joinability, e.mapping) for e in entries]

    # the session serves at its default rank='quality' + profile gate, so
    # the cold reference must run the raw engine with the same knobs
    cold = {
        qi: key(
            discover_many(
                idx, [distinct[qi]], k=common.K,
                rank="quality", profile_gate=True,
            )[0][0]
        )
        for qi in sorted(set(traffic.tolist()))
    }

    session = MateSession(
        idx,
        DiscoveryConfig(
            k=common.K, window=4, flush_after=None, result_cache=64, bound_cache=64
        ),
    )
    eng = DiscoveryEngine(session=session)
    lat = []
    identical = True
    for qi in traffic:
        q, q_cols = distinct[qi]
        t0 = time.perf_counter()
        req = eng.discover(q, q_cols)
        lat.append(time.perf_counter() - t0)
        identical &= key(req.results) == cold[qi]
    lat_us = np.asarray(lat) * 1e6
    hits = session.stats.cache_hits
    common.emit(
        "serving/zipf(128)", float(lat_us.mean()),
        f"hits={hits};hit_rate={hits / N_REQUESTS:.4f};"
        f"bit_identical={int(identical)};requests={N_REQUESTS};"
        f"p50_us={np.percentile(lat_us, 50):.1f};"
        f"p99_us={np.percentile(lat_us, 99):.1f}",
    )

    # second wave: the SAME queries at a different k — the result cache
    # cannot answer (k is part of its key) but the bound cache replays
    # phase A, skipping gather_candidates + the filter launch per request
    seen = sorted(set(traffic.tolist()))
    lat2 = []
    identical2 = True
    for qi in seen:
        q, q_cols = distinct[qi]
        t0 = time.perf_counter()
        req = eng.discover(q, q_cols, k=5)
        lat2.append(time.perf_counter() - t0)
        identical2 &= key(req.results) == key(
            discover_many(
                idx, [(q, q_cols)], k=5, rank="quality", profile_gate=True
            )[0][0]
        )
    lat2_us = np.asarray(lat2) * 1e6
    common.emit(
        "serving/zipf_rek(128)", float(lat2_us.mean()),
        f"bound_hits={session.stats.bound_hits};distinct={len(seen)};"
        f"bound_identical={int(identical2)};"
        f"p50_us={np.percentile(lat2_us, 50):.1f}",
    )


def table2_precision():
    print("# Table 2 analog: precision mean±std")
    for gname, n_rows in common.ROWS.items():
        queries = common.query_group(n_rows)
        for bits, hashes in ((128, HASHES_128), (512, HASHES_512)):
            for h in hashes:
                idx = common.index(h, bits)
                _, st = common.run_discovery(idx, queries)
                common.emit(
                    f"t2/{gname}/{h}({bits})", 0.0,
                    f"precision={st['precision_mean']:.3f}±{st['precision_std']:.3f}"
                )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default=None, choices=["index_build", "serving"],
        help="run a single section (CI's bench job gates index_build and "
             "serving without paying for the full table sweep)",
    )
    args = ap.parse_args(argv)
    if args.only == "serving":
        serving()
        common.save_trajectory("serving")
        return
    index_build()
    common.save_trajectory("index_build")
    if args.only == "index_build":
        return
    table1_runtime()
    table_engines()
    table2_precision()
    common.save_trajectory("tables")
    serving()
    common.save_trajectory("serving")


if __name__ == "__main__":
    main()
