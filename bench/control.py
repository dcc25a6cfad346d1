"""The control and the planted faults that ``correct`` has to catch.

    python3 bench/control.py --workload <cell> --fault <name> --seconds <s> --seeds <n> [<n> ...]

Each named fault breaks the timed path underneath a whole benchmark run, in
this process, and the run's ``correct`` must come out false:

- ``unverified``: the control.  Exact verification is the host step a
  faster program is tempted to skip: here a table's joinability is the
  number of query keys with a filter-surviving row in it, whatever columns
  they sit in, instead of the verified count under one mapping.
- ``stale``: a step that returns its state unchanged: every request after
  the first gets the answer scored for the request before it.
- ``half_candidates``: half of each request's candidate tables left out of
  the shared filter launch.
- ``altered``: an answer altered where it is produced: the first entry's
  joinability is off by one.

One line per seed: the result's ``correct`` and its checks.  The
benchmark's own runs never plant a fault.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def plant(name: str):
    """Plant fault ``name`` in the program; returns a function that removes it."""
    import numpy as np

    from repro.core import batched

    undo = []

    def swap(owner, attr, new):
        undo.append((owner, attr, getattr(owner, attr)))
        setattr(owner, attr, new)

    if name == "unverified":
        calculate_j = batched._calculate_j

        def unverified(index, plan, rows, hits):
            _, mapping = calculate_j(index, plan, rows, hits)
            return int(np.unique(np.nonzero(hits)[1]).size), mapping

        swap(batched, "_calculate_j", unverified)
    elif name == "stale":
        score = batched.score_from_counts
        last = []

        def stale(*args, **kwargs):
            out = score(*args, **kwargs)
            prev = last[0] if last else out
            last[:] = [out]
            return prev

        swap(batched, "score_from_counts", stale)
    elif name == "half_candidates":
        plan_query = batched.plan_query

        def half(*args, **kwargs):
            plan = plan_query(*args, **kwargs)
            keep = np.arange(plan.block.n_tables) < (plan.block.n_tables + 1) // 2
            block = batched._gate_block(plan.block, keep)
            return dataclasses.replace(plan, block=block, elig=plan.elig[: block.n_items])

        swap(batched, "plan_query", half)
    elif name == "altered":
        score = batched.score_from_counts

        def altered(*args, **kwargs):
            entries, stats = score(*args, **kwargs)
            if entries:
                entries = [dataclasses.replace(entries[0], joinability=entries[0].joinability + 1)] + entries[1:]
            return entries, stats

        swap(batched, "score_from_counts", altered)
    else:
        raise KeyError(f"no fault {name!r}")

    def remove():
        while undo:
            owner, attr, orig = undo.pop()
            setattr(owner, attr, orig)

    return remove


FAULTS = ("unverified", "stale", "half_candidates", "altered")


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    for seed in args.seeds:
        remove = plant(args.fault)
        try:
            result = run.run(args.workload, seed, args.seconds, False)
        finally:
            remove()
        print(json.dumps({
            "fault": args.fault, "seed": seed, "correct": result["correct"],
            "checks": result["checks"], "metrics": result["metrics"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
