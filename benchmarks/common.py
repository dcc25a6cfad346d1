"""Shared benchmark fixtures: corpus, query groups, index cache, timing,
and ``BENCH_*.json`` trajectory persistence (see docs/BENCHMARKS.md)."""

from __future__ import annotations

import json
import os
import sys
import time
from functools import lru_cache

sys.path.insert(0, __file__.rsplit("/", 2)[0] + "/src")

import numpy as np

from repro.core import discovery, xash
from repro.core.batched import discover_many, filter_outcomes
from repro.core.corpus import Corpus, Table
from repro.core.index import MateIndex
from repro.data import synthetic
from repro.kernels import registry

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def resolved_backend() -> str:
    """The registry-resolved filter backend this bench process runs under.

    Stamped into every trajectory row so ``tools/check_bench.py`` can refuse
    to compare runs recorded under different backends (a baseline recorded
    on the fused path must not be "regressed" by a composed-path run).
    """
    return registry.resolve_backend().name

SEED = 3
N_TABLES = 500
ROWS = {"webtable(10)": 10, "webtable(100)": 100}
N_QUERIES = 4
K = 10


@lru_cache(maxsize=1)
def corpus():
    return synthetic.make_corpus(
        synthetic.SyntheticSpec(n_tables=N_TABLES, seed=SEED)
    )


@lru_cache(maxsize=None)
def index(hash_name: str = "xash", bits: int = 128, **xash_kw):
    c = corpus()
    if hash_name == "xash":
        kw = dict(xash_kw)
        cfg = xash.XashConfig(
            bits=bits, char_freq=tuple(c.char_frequencies().tolist()), **kw
        )
        return MateIndex(c, cfg=cfg)
    return MateIndex(c, cfg=xash.XashConfig(bits=bits), hash_name=hash_name)


@lru_cache(maxsize=None)
def routed_index(n_shards: int = 4, bits: int = 128):
    """Routed lake over the bench corpus: per-shard ownership, shard-local
    launches, count-only merge (``core.routing.ShardedMateIndex``)."""
    from repro.core.routing import ShardedMateIndex

    c = corpus()
    cfg = xash.XashConfig(
        bits=bits, char_freq=tuple(c.char_frequencies().tolist())
    )
    return ShardedMateIndex(c, cfg=cfg, n_shards=n_shards)


@lru_cache(maxsize=None)
def query_group(n_rows: int, key_width: int = 2):
    return tuple(
        synthetic.make_mixed_queries(
            corpus(), N_QUERIES, n_rows, key_width, seed=SEED + 2
        )
    )


def planted_quality_lake(
    n_keys: int = 20,
    n_good: int = 10,
    n_bad: int = 10,
    n_narrow: int = 10,
    n_noise: int = 30,
    noise_seed: int = 11,
):
    """Deterministic lake separating count rank from quality rank
    (``bench_ranking``'s planted lake, shared so other sections can reuse
    the shape).  Returns (corpus, query, q_cols, good_ids):

      * ``good`` tables hold each composite key exactly once — joinability
        ``n_keys``, uniqueness ~1.0;
      * ``bad`` tables hold the same keys plus repeated filler rows — the
        SAME joinability, uniqueness ~0.2; good/bad ids interleave so count
        rank alternates the classes;
      * ``narrow`` 1-column tables hold the init-column values — posting
        candidates that can never host a width-2 key (profile-gate fodder);
      * ``noise`` tables come from the seeded synthetic generator.
    """
    keys = [(f"pkA{r:02d}", f"pkB{r:02d}") for r in range(n_keys)]
    query = Table(
        -1, [[a, b, f"qx{r:02d}"] for r, (a, b) in enumerate(keys)]
    )
    tables: list[Table] = []
    good_ids: set[int] = set()
    # good/bad interleaved: even ids good, odd ids bad
    for i in range(n_good + n_bad):
        tid = len(tables)
        cells = [[a, b, f"t{tid}v{r}"] for r, (a, b) in enumerate(keys)]
        if i % 2:  # bad: dilute every column with repeated filler rows
            cells += [[f"pad{tid}", f"pad{tid}", f"pad{tid}"]] * (4 * n_keys)
        else:
            good_ids.add(tid)
        tables.append(Table(tid, cells))
    for _ in range(n_narrow):  # candidates the gate must prune
        tid = len(tables)
        tables.append(Table(tid, [[a] for a, _b in keys]))
    noise = synthetic.make_corpus(
        synthetic.SyntheticSpec(n_tables=n_noise, seed=noise_seed)
    )
    for t in noise.tables:
        tables.append(Table(len(tables), t.cells))
    return Corpus(tables), query, [0, 1], good_ids


def fp_outcomes(idx, queries, check_false_negatives: bool = False) -> dict:
    """Aggregate unpruned §6.3 filter outcomes over a query group.

    Sums ``core.batched.filter_outcomes`` per query and derives ``fp_rate``
    (false positives per eligible probe) — the Table 1/2 quantity the
    hash-width sweep in ``bench_fp_rate.py`` tracks.
    """
    agg = {"checks": 0, "passed": 0, "tp": 0, "fp": 0, "fn": 0}
    for q, q_cols in queries:
        out = filter_outcomes(
            idx, q, q_cols, check_false_negatives=check_false_negatives
        )
        for key in agg:
            agg[key] += out[key]
    agg["fp_rate"] = agg["fp"] / max(agg["checks"], 1)
    return agg


def run_discovery(idx, queries, k=K, row_filter=True, engine="seq"):
    """Returns (seconds_total, aggregate stats).

    Engines: ``seq`` (faithful Alg. 1), ``batched`` (the kernel-backed
    engine, one request per filter launch, registry-resolved backend),
    ``batched_np`` (same engine, backend='numpy'), ``many`` (all queries
    share one filter launch — the DiscoveryEngine path), plus
    ``batched_fused`` / ``many_fused`` (backend='fused': the fused
    filter+segment-count kernel — counts-only readback, zero match-matrix
    bytes), and ``batched_gather`` / ``many_gather`` (backend='fused-gather':
    the gather-fused launch — candidate superkeys are DMA-gathered from the
    device-resident store inside the kernel, so the host ships only int32
    row offsets; ``gather_saved`` below counts the bytes that never moved).
    """
    tp = fp = checks = passed = 0
    mat_bytes = rb_bytes = 0
    precs = []
    t0 = time.perf_counter()
    if engine in ("many", "many_fused", "many_gather"):
        many_backend = {"many_fused": "fused", "many_gather": "fused-gather"}
        stats = [
            st
            for _, st in discover_many(
                idx,
                [(q, c) for q, c in queries],
                k=k,
                backend=many_backend.get(engine),
            )
        ]
    else:
        solo_backend = {
            "batched": None,
            "batched_fused": "fused",
            "batched_gather": "fused-gather",
            "batched_np": "numpy",
        }
        stats = []
        for q, q_cols in queries:
            if engine in solo_backend:
                _, st = discover_many(
                    idx, [(q, q_cols)], k=k, backend=solo_backend[engine]
                )[0]
            else:
                _, st = discovery.discover(idx, q, q_cols, k=k, row_filter=row_filter)
            stats.append(st)
    dt = time.perf_counter() - t0
    fused_launches = 0
    gather_saved = 0
    shard_launches = 0
    route_bytes = 0
    items_checked = 0
    for st in stats:
        tp += st.verified_tp
        fp += st.verified_fp
        checks += st.filter_checks
        passed += st.filter_passed
        mat_bytes += st.filter_matrix_bytes
        rb_bytes += st.filter_readback_bytes
        fused_launches += st.filter_fused_launches
        gather_saved += st.gather_bytes_saved
        shard_launches += st.shard_launches
        route_bytes += st.route_bytes_merged
        items_checked += st.pl_items_checked
        precs.append(st.precision)
    return dt, {
        "tp": tp,
        "fp": fp,
        "checks": checks,
        "passed": passed,
        "matrix_bytes": mat_bytes,
        "readback_bytes": rb_bytes,
        "fused_launches": fused_launches,
        "gather_saved": gather_saved,
        "shard_launches": shard_launches,
        "route_bytes": route_bytes,
        "items_checked": items_checked,
        "precision_mean": float(np.mean(precs)),
        "precision_std": float(np.std(precs)),
    }


ROWS_CSV = []


def emit(name: str, us_per_call: float, derived: str, backend: str | None = None):
    """Record one bench row.  ``backend`` overrides the row's backend stamp
    for rows that PIN a backend in code (``engine='batched_fused'`` and
    friends) rather than following the process-level registry resolution —
    the stamp must describe what the row actually ran."""
    ROWS_CSV.append((name, us_per_call, derived, backend))
    print(f"{name},{us_per_call:.1f},{derived}")


def save_trajectory(section: str) -> str:
    """Append this run's rows to ``benchmarks/results/BENCH_<section>.json``.

    Each file is a JSON list of run records ({"ts", "backend", "rows"}) so
    successive runs accumulate a perf trajectory; rows emitted since the
    last save are consumed.  Every row (and the record itself) carries the
    registry-resolved filter backend, so downstream comparisons
    (``tools/check_bench.py``, ``tools/plot_bench.py``) can tell apart runs
    recorded under different dispatch paths.  Returns the file path.
    """
    global ROWS_CSV
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"BENCH_{section}.json")
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
        except (json.JSONDecodeError, OSError):
            history = []
    backend = resolved_backend()
    history.append({
        "ts": time.time(),
        "backend": backend,
        "rows": [
            {"name": n, "us_per_call": us, "derived": d, "backend": bk or backend}
            for n, us, d, bk in ROWS_CSV
        ],
    })
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
    ROWS_CSV = []
    return path
