"""Find a cell's knee: the highest rate its server sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --requests <n> --fractions <f> [<f> ...]

One process sets the cell up once for ``--requests`` requests (the lake,
the index and the warm-up of every group those requests can form), then
offers them twice over: first all at once, which measures the saturated
throughput (requests over the seconds the burst took), then open-loop at
each rate of ``--rates``, where a rate under the knee answers about as
many requests by the window's close as it offers and keeps its latency
flat from the first quarter of the window to the last.  Prints one JSON
line per pass.  The cell's rate is then fixed in its traffic file; runs of
the benchmark never search for one.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run, traffic, window  # noqa: E402
from bench.catalog import Catalog  # noqa: E402
from bench.lake import rng as seeded  # noqa: E402


def summary(outcomes, t0: float, seconds: float) -> dict:
    lat = window.latencies(outcomes)
    q = max(len(lat) // 4, 1)
    return {
        "offered": len(outcomes),
        "answered": int(lat.size),
        "answered_by_close": round(window.completed_rate(outcomes, t0, seconds) * seconds),
        "p50_s": window.percentile(lat, 50),
        "p95_s": window.percentile(lat, 95),
        "first_quarter_mean_s": float(np.mean(lat[:q])),
        "last_quarter_mean_s": float(np.mean(lat[-q:])),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--fractions", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cat = Catalog()
    rate = cat.traffic(cat.cell(args.workload)["traffic"])["rate"]
    prep = run.prepare(cat, args.workload, args.seed, args.requests / rate)
    n = len(prep.queries)

    t = time.perf_counter()
    outcomes, t0 = run.serve(prep.session, prep.queries, [0.0] * n, 0.0, grace=1200.0)
    burst = time.perf_counter() - t
    print(json.dumps({"pass": "burst", "seconds": burst, "throughput": n / burst,
                      **summary(outcomes, t0, burst)}), flush=True)
    rng = seeded(args.seed, 9)
    for f in args.fractions:
        r = f * n / burst
        seconds = n / r
        dues = traffic.arrivals(r, n, seconds, rng)
        outcomes, t0 = run.serve(prep.session, prep.queries, list(dues), seconds, grace=600.0)
        print(json.dumps({"pass": "open", "fraction": f, "rate": r,
                          **summary(outcomes, t0, seconds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
