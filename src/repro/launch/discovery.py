"""MATE discovery service driver:
``python -m repro.launch.discovery [--n-tables 400] [--queries 5] [--hash xash]
[--bits 128|256|512] [--backend fused|pallas|xla|numpy|auto]``

End-to-end run of the paper's system on a synthetic lake through the unified
``MateSession`` surface: build the session (offline phase), run top-k n-ary
join discovery (online phase) with both the faithful Algorithm 1 engine and
the session's batched engine, and report the paper's metrics (precision, FP
counts, filtering power, runtimes).

``--backend`` pins the §6.3 filter backend through ``DiscoveryConfig`` — the
highest-precedence level of the registry (config > ``MATE_FILTER_BACKEND`` >
platform default); omitted, the session resolves it per that rule.

``--mesh dxm`` additionally runs the shard_map-distributed filter to show
the corpus-sharded layout (1x1 on CPU; 16x16 on a real pod).

``--build-mesh N`` shards the OFFLINE phase the same way: the session builds
over an N-device mesh (``MateSession.build(..., mesh=...)`` — unique-value
hashing under shard_map, host-side posting merge), forcing N virtual CPU
devices for a dry run when the host has fewer.  The build is byte-identical
to the single-host pass; the driver prints the ``BuildStats`` breakdown.

``--route-shards N`` builds a ROUTED lake on top: a ``ShardedMateIndex``
(``MateSession.build(..., distributed=True, n_shards=N)``) that keeps each
shard's postings, superkeys, and device store resident where the shard was
built and routes every query to the data — only int32 per-table count
vectors cross a shard boundary.  The driver replays the same queries
through the routed session, asserts bit-identical top-k against the
single-host engines, and prints the cross-shard traffic
(``route_bytes_merged``) next to the superkey bytes a host-gather path
would have shipped.
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

import jax

from repro.core import discovery
from repro.core import fd as fd_lib
from repro.core.corpus import Table
from repro.core.session import DiscoveryConfig, MateSession
from repro.core import distributed
from repro.data import synthetic
from repro.kernels import registry
from repro.launch import mesh as meshlib
from repro.serve.engine import DiscoveryEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-tables", type=int, default=400)
    ap.add_argument("--queries", type=int, default=5)
    ap.add_argument("--rows", type=int, default=25)
    ap.add_argument("--key-width", type=int, default=2)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--hash", default="xash",
                    choices=["xash", "bf", "ht", "murmur", "md5", "city", "simhash"])
    ap.add_argument("--bits", type=int, default=128, choices=[128, 256, 512],
                    help="superkey hash width (uint32 lanes = bits/32)")
    ap.add_argument("--backend", default=None, choices=registry.backend_names(),
                    help="filter backend (config-level pin; default: "
                         "MATE_FILTER_BACKEND, then platform default)")
    ap.add_argument("--rank", default="quality", choices=["quality", "count"],
                    help="result ordering: join-quality scoring head "
                         "(default) or exact-joinability count order; the "
                         "verified top-k SET is identical either way")
    ap.add_argument("--no-profile-gate", action="store_true",
                    help="disable the column-profile candidate gate "
                         "(pure pruning; results are set-identical with it "
                         "on or off)")
    ap.add_argument("--flush-after", type=float, default=None,
                    help="serving deadline (s) for partial DiscoveryEngine groups")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded submit queue: admission control kicks in at "
                         "this many waiting requests (default: unbounded)")
    ap.add_argument("--pressure-policy", default="shed",
                    choices=["shed", "degrade"],
                    help="at max_queue: reject with AdmissionError, or admit "
                         "at degraded 128-bit filtering (still bit-identical)")
    ap.add_argument("--fds", action="store_true",
                    help="also run the FD workload (core.fd): test a "
                         "candidate functional dependency det-cols -> "
                         "dependent against every joining lake table, no "
                         "join materialized")
    ap.add_argument("--fd-signals", action="store_true",
                    help="order FD candidates by the multi-signal ensemble "
                         "(joinability + uniqueness + sketch + name) instead "
                         "of raw support")
    ap.add_argument("--result-cache", type=int, default=0,
                    help="query-result cache capacity (0: off) — repeated "
                         "queries answer at submit, invalidated on mutations")
    ap.add_argument("--bound-cache", type=int, default=0,
                    help="hot-table bound cache capacity (0: off) — warm "
                         "queries skip gather+filter at any k")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--build-mesh", type=int, default=1, metavar="N",
                    help="shard the offline index build over an N-device mesh "
                         "(forces N virtual CPU devices when the host has "
                         "fewer and jax is not yet initialised)")
    ap.add_argument("--route-shards", type=int, default=0, metavar="N",
                    help="also build an N-shard routed lake "
                         "(ShardedMateIndex) and replay the queries through "
                         "it: shard-local filter launches, count-only merge, "
                         "bit-identical top-k asserted against single-host")
    ap.add_argument("--seed", type=int, default=3)
    args = ap.parse_args(argv)

    if args.build_mesh > 1 or args.route_shards > 1:
        # must win the race with the first jax backend init; harmless if the
        # backend is already up — the mesh is clamped to visible devices below
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            n_force = max(args.build_mesh, args.route_shards)
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{n_force}"
            ).strip()

    print(f"[mate] building corpus ({args.n_tables} tables) ...")
    corpus = synthetic.make_corpus(
        synthetic.SyntheticSpec(n_tables=args.n_tables, seed=args.seed)
    )
    config = DiscoveryConfig(
        bits=args.bits, k=args.k, backend=args.backend, hash_name=args.hash,
        rank=args.rank, profile_gate=not args.no_profile_gate,
        flush_after=args.flush_after, max_queue=args.max_queue,
        pressure_policy=args.pressure_policy, result_cache=args.result_cache,
        bound_cache=args.bound_cache,
        signals=fd_lib.DEFAULT_SIGNALS if args.fd_signals else None,
    )
    build_mesh = None
    if args.build_mesh > 1:
        n_dev = min(args.build_mesh, len(jax.devices()))
        if n_dev < args.build_mesh:
            print(
                f"[mate] --build-mesh {args.build_mesh}: only "
                f"{len(jax.devices())} devices visible (jax already "
                f"initialised?), building on {n_dev}"
            )
        build_mesh = meshlib.make_mesh((n_dev,), ("data",))
    t0 = time.time()
    session = MateSession.build(corpus, config, mesh=build_mesh)
    index = session.index
    print(
        f"[mate] offline phase: indexed {corpus.total_rows} rows, "
        f"{len(corpus.unique_values)} unique values in {time.time()-t0:.2f}s "
        f"(hash={args.hash}, bits={session.bits}, lanes={index.cfg.lanes}, "
        f"backend={session.backend.name}[{session.backend.source}])"
    )
    bs = session.build_stats
    print(
        f"[mate] build stats: shards={bs.n_shards}"
        f"{'' if bs.mesh_shape is None else f' mesh={bs.mesh_shape}'} "
        f"hash={bs.hash_seconds:.2f}s superkeys={bs.superkey_seconds:.2f}s "
        f"postings={bs.postings_seconds:.2f}s merge={bs.merge_seconds:.3f}s "
        f"({bs.bytes_hashed} bytes hashed over "
        f"{bs.values_total} unique values)"
    )

    queries = synthetic.make_mixed_queries(
        corpus, args.queries, args.rows, args.key_width, seed=args.seed + 2
    )
    agg = {"tp": 0, "fp": 0, "checks": 0, "t_seq": 0.0, "t_batched": 0.0,
           "mat_bytes": 0, "rb_bytes": 0}
    for qi, (q, q_cols) in enumerate(queries):
        t0 = time.time()
        topk_seq, st = discovery.discover(index, q, q_cols, k=args.k)
        agg["t_seq"] += time.time() - t0
        t0 = time.time()
        topk_bat, stb = session.discover(q, q_cols)
        agg["t_batched"] += time.time() - t0
        agg["tp"] += st.verified_tp
        agg["fp"] += st.verified_fp
        agg["checks"] += st.filter_checks
        agg["mat_bytes"] += stb.filter_matrix_bytes
        agg["rb_bytes"] += stb.filter_readback_bytes
        # quality rank reorders the session's entries by the scoring head;
        # the scalar engine is count-ordered — the invariant across rank
        # modes is the verified SET, so compare sorted under 'quality'.
        key_seq = [(e.table_id, e.joinability) for e in topk_seq]
        key_bat = [(e.table_id, e.joinability) for e in topk_bat]
        match = (
            sorted(key_seq) == sorted(key_bat)
            if config.rank == "quality"
            else key_seq == key_bat
        )
        label = (
            "engines_set_identical" if config.rank == "quality"
            else "engines_bit_identical"
        )
        print(
            f"[mate] query {qi}: top-{args.k} "
            f"{[(e.table_id, e.joinability) for e in topk_seq[:5]]}... "
            f"precision={st.precision:.3f} {label}={match}"
        )
    prec = agg["tp"] / max(agg["tp"] + agg["fp"], 1)
    if agg["mat_bytes"]:
        readback = (
            f"match_readback={agg['rb_bytes']}/{agg['mat_bytes']}B "
            f"({agg['rb_bytes'] / agg['mat_bytes']:.1%} of full matrix)"
        )
    else:  # fused counts-only path: no match matrix was ever produced
        readback = f"match_readback={agg['rb_bytes']}B (fused, matrix_bytes=0)"
    print(
        f"[mate] total: precision={prec:.3f} filter_checks={agg['checks']} "
        f"seq={agg['t_seq']:.2f}s batched={agg['t_batched']:.2f}s "
        f"speedup={agg['t_seq']/max(agg['t_batched'],1e-9):.1f}x " + readback
    )
    print(
        f"[mate] profile gate ({'on' if config.profile_gate else 'off'}, "
        f"rank={config.rank}): tables_gated={session.stats.tables_gated} "
        f"gate_bytes_saved={session.stats.gate_bytes_saved}B "
        f"ranking_launches={session.stats.ranking_launches}"
    )

    if args.fds and queries:
        # FD workload demo: extend the first query with a synthetic dependent
        # column (one value per determinant key, FD-clean), then duplicate
        # one key with a CONFLICTING dependent value so a violating group
        # exists — tables matching that key must come back holds=False.
        q0, qc0 = queries[0]
        dep_col = q0.n_cols
        cells = [list(row) + [f"dep{i}"] for i, row in enumerate(q0.cells)]
        cells.append(list(q0.cells[0]) + ["dep-conflict"])
        fd_query = Table(-1, cells, name="fd probe")
        t0 = time.time()
        fds, fstats = session.discover_fds(
            fd_query, list(qc0), dep_col, min_support=1
        )
        print(
            f"[mate] FD workload (det={list(qc0)} -> dep={dep_col}, "
            f"signals={'on' if config.signals else 'off'}): "
            f"candidates={fstats.fd_candidates} "
            f"validated={fstats.fd_validated} "
            f"pruned={fstats.fd_candidates - fstats.fd_validated} "
            f"bytes_verified={fstats.fd_bytes_verified}B "
            f"in {time.time()-t0:.3f}s"
        )
        for c in fds[:5]:
            score = "" if c.score is None else f" score={c.score:.3f}"
            print(
                f"[mate]   table {c.table_id}: support={c.support} "
                f"holds={c.holds} violations={c.violations}{score}"
            )

    # multi-query serving path: requests share filter launches in slot
    # groups (the shared launch costs O(rows x keys) of the whole group,
    # so it is bounded rather than fused across arbitrarily many queries).
    # The engine wraps the SAME session: one config, one resolved backend.
    engine = DiscoveryEngine(
        session=session, batch=min(max(len(queries), 1), 16),
        flush_after=args.flush_after,
    )
    reqs = [engine.submit(q, q_cols) for q, q_cols in queries]
    t0 = time.time()
    served = engine.flush()
    t_many = time.time() - t0
    agree = all(r.done and r.future.done() and r.stats is not None for r in reqs)
    print(
        f"[mate] DiscoveryEngine: {len(served)} requests in shared filter "
        f"launches of ≤{engine.batch} "
        f"({t_many:.2f}s, vs {agg['t_seq']:.2f}s sequential, all_served={agree})"
    )
    if args.result_cache or args.bound_cache:
        # replay the same traffic: repeats answer from the serving caches
        t0 = time.time()
        replay = [engine.discover(q, q_cols) for q, q_cols in queries]
        t_replay = time.time() - t0
        hot = all(r.from_cache for r in replay) if args.result_cache else True
        print(
            f"[mate] serving caches: replayed {len(replay)} requests in "
            f"{t_replay:.3f}s (cache_hits={session.stats.cache_hits}, "
            f"bound_hits={session.stats.bound_hits}, all_from_cache={hot}, "
            f"shed={session.stats.shed}, degraded={session.stats.degraded})"
        )
    print(f"[mate] session: {session}")

    if args.route_shards > 1:
        t0 = time.time()
        routed = MateSession.build(
            corpus, config, distributed=True, n_shards=args.route_shards
        )
        t_build = time.time() - t0
        lanes = routed.index.cfg.lanes
        identical = True
        items = 0
        t0 = time.time()
        for qi, (q, q_cols) in enumerate(queries):
            topk_ref, _ = session.discover(q, q_cols)
            topk_rt, st_rt = routed.discover(q, q_cols)
            items += st_rt.pl_items_checked
            # both sessions share the rank mode, so even the quality order
            # should agree (identical profiles shard-merged vs global); the
            # asserted invariant stays the exact entry sequence.
            identical &= [(e.table_id, e.joinability) for e in topk_ref] == [
                (e.table_id, e.joinability) for e in topk_rt
            ]
        t_routed = time.time() - t0
        host_gather_bytes = items * lanes * 4  # superkeys a host-gather ships
        rs = routed.stats
        print(
            f"[mate] routed lake ({routed.index.n_shards} shards, built in "
            f"{t_build:.2f}s): {len(queries)} queries in {t_routed:.2f}s, "
            f"bit_identical={identical}, shard_launches={rs.shard_launches}, "
            f"gather_demotions={rs.shard_gather_demotions}"
        )
        print(
            f"[mate] routed traffic: route_bytes_merged="
            f"{rs.route_bytes_merged}B crossed shard boundaries vs "
            f"{host_gather_bytes}B of superkeys a host-gather path ships "
            f"({rs.route_bytes_merged / max(host_gather_bytes, 1):.1%}); "
            f"superkey rows crossing shards: 0 (by construction)"
        )
        if not identical:
            raise SystemExit("[mate] routed top-k diverged from single-host")

    if not queries:
        return
    dp, tp_ = (int(x) for x in args.mesh.split("x"))
    mesh = meshlib.make_mesh((dp, tp_), ("data", "model"))
    row_tables = np.asarray(
        corpus.table_of_row(np.arange(corpus.total_rows)), dtype=np.int32
    )
    sk, rt = distributed.shard_corpus_rows(
        index.superkeys, row_tables, mesh, ("data",)
    )
    q, q_cols = queries[0]
    _keys, sk_of_key = discovery.build_query_superkeys(index, q, q_cols)
    qsk = np.stack(list(sk_of_key.values()))
    # the distributed filter resolves its per-shard impl from the same
    # registry precedence (a fused backend runs the fused shard launch)
    fn = distributed.make_distributed_filter(
        mesh, len(corpus.tables), ("data",), backend=session.backend
    )
    t0 = time.time()
    tc, kc = fn(sk, rt, qsk)
    tc.block_until_ready()
    print(
        f"[mate] distributed filter on mesh {args.mesh} "
        f"(impl={distributed.shard_impl_for(session.backend)}): "
        f"{int(np.asarray(tc).sum())} candidate rows across "
        f"{int((np.asarray(tc) > 0).sum())} tables in {time.time()-t0:.3f}s"
    )


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
