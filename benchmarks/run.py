"""Benchmark harness: one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick]``
prints ``name,us_per_call,derived`` CSV rows (plus section comments), then a
roofline summary if dry-run results exist.

A section that raises is reported (traceback to stderr) and the remaining
sections still run, but the process exits NON-ZERO — CI's bench-regression
gate (tools/check_bench.py) must be able to trust that every row it compares
was actually produced, so a silently skipped section is a gate failure.
"""

from __future__ import annotations

import argparse
import sys
import traceback

sys.path.insert(0, __file__.rsplit("/", 2)[0] + "/src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="skip 512-bit builds")
    args = ap.parse_args()

    from benchmarks import (
        bench_fd, bench_figures, bench_fp_rate, bench_kernels,
        bench_ranking, bench_tables, common,
    )

    if args.quick:
        bench_tables.HASHES_512 = []
        bench_tables.HASHES_128 = ["murmur", "ht", "bf", "xash"]
        bench_tables.ENGINE_512 = False

    failures: list[str] = []

    def section(name: str, fn) -> None:
        try:
            fn()
        except Exception:
            failures.append(name)
            # drop rows the failed section emitted but never saved, so they
            # can't leak into the NEXT section's BENCH_*.json trajectory
            common.ROWS_CSV = []
            print(f"# SECTION FAILED: {name}", flush=True)
            traceback.print_exc()

    # every row this process emits is stamped with ONE resolved backend —
    # announce it up front so a pasted CSV is self-describing too
    print(f"# filter_backend={common.resolved_backend()} (registry-resolved)")
    print("name,us_per_call,derived")
    section("tables", lambda: bench_tables.main([]))
    section("figures", bench_figures.main)
    section("kernels", bench_kernels.main)
    section("ranking", lambda: bench_ranking.main([]))
    section("fd", lambda: bench_fd.main([]))
    # the width sweep exists to build 512-bit indexes — skipped entirely in
    # quick mode (run `benchmarks.bench_fp_rate --quick` directly for a
    # small-group 128/512 trend, as CI's bench job does)
    if not args.quick:
        section("fp_rate", lambda: bench_fp_rate.main([]))

    # roofline summary (requires results/dryrun/*.json from the dry-run;
    # their absence is expected on hosts that never ran it — not a failure)
    try:
        from benchmarks import roofline

        cells = roofline.load_cells(variant="baseline")
        rows = [t for t in (roofline.terms(c) for c in cells) if t]
        if rows:
            by_dom = {}
            for r in rows:
                by_dom.setdefault(r["dominant"], []).append(r)
            for dom, rs in sorted(by_dom.items()):
                common.emit(
                    f"roofline/{dom}-bound-cells", 0.0,
                    f"count={len(rs)};median_frac="
                    f"{sorted(x['roofline_frac'] for x in rs)[len(rs)//2]:.3f}"
                )
    except Exception as e:  # dry-run not yet executed
        print(f"# roofline summary unavailable: {e}")

    if failures:
        print(f"# FAILED sections: {', '.join(failures)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
