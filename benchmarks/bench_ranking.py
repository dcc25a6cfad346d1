"""Ranked discovery bench (ISSUE 9): precision@k of the join-quality
scoring head vs raw count rank, plus profile-gate prune accounting.

The planted-quality lake makes count rank provably uninformative:

  * ``good`` tables hold each of the query's composite keys exactly once
    and nothing else duplicated — joinability 20, uniqueness ~1.0;
  * ``bad`` tables hold the SAME keys once each plus a block of repeated
    filler rows — joinability is identical (20) but uniqueness ~0.2;
  * good/bad ids interleave, so count rank (sorted ``(-J, table_id)``)
    alternates them and precision@10 sits at 0.5, while the quality score's
    uniqueness term separates the two classes completely;
  * ``narrow`` tables are 1-column tables holding the init-column values —
    posting-list candidates that can never host a width-2 key, so the
    profile gate prunes them deterministically (``n_cols < width``).

Retrieval runs at k = all planted tables: rank='quality' must keep the
verified SET bit-identical to count rank (pure reordering), which is
exactly what the ``identical`` flag gates in CI.
"""

from __future__ import annotations

import argparse
import time

from benchmarks import common
from repro.core import xash
from repro.core.batched import discover_many
from repro.core.index import MateIndex

N_KEYS = 20
N_GOOD = 10
N_BAD = 10
N_NARROW = 10
N_NOISE = 30
PREC_AT = 10
BITS = 128


def planted_lake():
    """Returns (corpus, query, q_cols, good_ids) — the shared factory at
    this module's historical parameters (byte-identical lake)."""
    return common.planted_quality_lake(
        n_keys=N_KEYS, n_good=N_GOOD, n_bad=N_BAD,
        n_narrow=N_NARROW, n_noise=N_NOISE, noise_seed=11,
    )


def _precision_at(entries, good_ids, n=PREC_AT):
    top = [e.table_id for e in entries[:n]]
    return sum(1 for tid in top if tid in good_ids) / max(len(top), 1)


def ranking_bench():
    print("# quality rank vs count rank on the planted-quality lake")
    corpus, query, q_cols, good_ids = planted_lake()
    idx = MateIndex(corpus, cfg=xash.XashConfig(bits=BITS))
    k = N_GOOD + N_BAD  # retrieve every planted table; rank decides order

    count_rank, count_stats = discover_many(idx, [(query, q_cols)], k=k)[0]
    t0 = time.perf_counter()
    quality, qstats = discover_many(
        idx, [(query, q_cols)], k=k, rank="quality", profile_gate=True
    )[0]
    dt_us = (time.perf_counter() - t0) * 1e6

    def key(entries):
        return sorted((e.table_id, e.joinability) for e in entries)

    identical = key(quality) == key(count_rank)
    prec_q = _precision_at(quality, good_ids)
    prec_c = _precision_at(count_rank, good_ids)
    common.emit(
        f"rank/planted({BITS})", dt_us,
        f"prec_quality={prec_q:.3f};prec_count={prec_c:.3f};"
        f"quality_beats_count={prec_q > prec_c};"
        f"n_good={N_GOOD};n_bad={N_BAD};k={k};"
        f"ranking_launches={qstats.ranking_launches}",
    )

    fetched = count_stats.tables_fetched
    gated = qstats.tables_gated
    prune_rate = gated / max(fetched, 1)
    common.emit(
        f"rank/gate({BITS})", 0.0,
        f"gated={gated};fetched={fetched};prune_rate={prune_rate:.3f};"
        f"identical={identical};gate_bytes_saved={qstats.gate_bytes_saved}",
    )
    return {
        "prec_quality": prec_q, "prec_count": prec_c,
        "gated": gated, "identical": identical,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.parse_args(argv)
    out = ranking_bench()
    common.save_trajectory("ranking")
    return out


if __name__ == "__main__":
    main()
