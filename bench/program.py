"""Readings of the program's own telemetry (``repro.telemetry``) in a run.

    python3 bench/program.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ``bench/run.py``'s run of the cell with the program's tracer switched
on from before the lake is built, and prints the run's result line with
one more key, ``program``: what ``readings`` takes from the spans of the
serving window (those under a ``serve.pump_turn`` or ``serve.wait``).
``bench/run.py`` itself is the same run with the tracer off.  With ``--trace 1`` it also splits the traced window's
device-idle time among the program's spans (``sweep``), adds the share of
the window in which the device idles while a request group is served
(``device_wait_on_host_pct``), and logs the split as one
``[bench] program gaps:`` line.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
PREFIX = "mate."
ROOTS = ("serve.pump_turn", "serve.wait")


def window_spans(records) -> list:
    """The records of the serving window: ``serve.*`` roots and everything
    under a pump turn.  Set-up and warm-up call the session directly and
    open no such root, so none of their spans count."""
    kids: dict = {}
    for s in records:
        kids.setdefault(s.parent, []).append(s)
    out = [s for s in records if s.parent is None and s.name in ROOTS]
    todo = list(out)
    while todo:
        s = todo.pop()
        for c in kids.get(s.sid, []):
            out.append(c)
            todo.append(c)
    return out


def _ms(s) -> float:
    return (s.t1_ns - s.t0_ns) * 1e-6


def readings(records) -> dict:
    """Per-layer readings of one run's records; a reading with nothing to
    read is left out."""
    spans = window_spans(records)
    by: dict[str, list] = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    get = lambda name: by.get(name, [])  # noqa: E731
    total = lambda name, key: sum(s.attrs.get(key, 0) for s in get(name))  # noqa: E731
    out: dict[str, float] = {}
    scored, planned = get("score.request"), get("plan.query")
    if scored:
        n = len(scored)
        out["verify_exact_ms"] = 1e3 * total("score.tables", "exact_s") / n
        out["verify_regather_ms"] = 1e3 * total("score.tables", "regather_s") / n
        out["rank_ms"] = sum(_ms(s) for s in get("score.rank")) / n
        out["pairs_verified"] = total("score.tables", "pairs_verified") / n
        out["score_request_ms"] = sum(_ms(s) for s in scored) / n
        inside = {s.sid for s in scored}
        covered = sum(_ms(s) for s in spans if s.parent in inside)
        out["score_children_share"] = covered / sum(_ms(s) for s in scored)
    if planned:
        n = len(planned)
        out["plan_query_ms"] = sum(_ms(s) for s in planned) / n
        out["launch_ms"] = sum(_ms(s) for s in get("filter.launch")) / n
        out["h2d_bytes"] = total("filter.launch", "h2d_bytes") / n
        out["elig_bytes"] = total("filter.launch", "elig_bytes") / n
        tables = total("plan.query", "tables")
        if tables:
            out["rule_pruned_share"] = total("score.tables", "tables_pruned") / tables
    waits = [w for s in get("serve.group") for w in s.attrs.get("waits", [])]
    if waits:
        out["queue_wait_ms"] = 1e3 * sum(waits) / len(waits)
    turns = get("serve.pump_turn")
    if turns:
        out["pump_turn_ms"] = sum(_ms(s) for s in turns) / len(turns)
    if scored:
        out["spans_per_request"] = len(spans) / len(scored)
    return out


def annotations(planes: list[dict]) -> list[tuple[float, float, str]]:
    """The program's spans as the profiler recorded them: ``mate.<name>``
    events on the host planes, as ``(start_ns, end_ns, name)``."""
    from bench import trace

    out = []
    for plane in planes:
        if trace.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, t, d in line["events"]:
                if name.startswith(PREFIX):
                    out.append((t, t + d, name[len(PREFIX):]))
    return out


# an annotation and its record agree on their duration to within this
MATCH_NS = 20_000.0
# offsets are voted for in buckets of this width
BUCKET_NS = 100_000.0


def on_trace_clock(records, planes: list[dict]) -> list[tuple[float, float, str]]:
    """``records`` as ``(start_ns, end_ns, name)`` on the trace's clock.

    The profiler drops an annotation that was open when it started or
    stopped (the pump's sleep at the traced window's edges, say), so the
    sweep places the records themselves.  Each annotation votes for
    ``start - t0`` of every record of its name and duration; the offset is
    the median of the votes in the most-voted bucket.  Empty when no
    annotation matches a record."""
    by: dict[str, list] = {}
    for r in records:
        by.setdefault(r.name, []).append(r)
    votes: dict[int, list[float]] = {}
    for t0, t1, name in annotations(planes):
        for r in by.get(name, []):
            if abs((r.t1_ns - r.t0_ns) - (t1 - t0)) <= MATCH_NS:
                off = t0 - r.t0_ns
                votes.setdefault(round(off / BUCKET_NS), []).append(off)
    if not votes:
        return []
    best = sorted(max(votes.values(), key=len))
    off = best[len(best) // 2]
    return [(r.t0_ns + off, r.t1_ns + off, r.name) for r in records]


def sweep(planes: list[dict], spans: list[tuple[float, float, str]], n_devices: int = 1) -> dict:
    """Split every device-idle instant of the traced window among ``spans``
    (``(start_ns, end_ns, name)`` on the trace's clock): at each instant the
    innermost open span (the latest to start, the shortest on a tie) takes
    the time, and idle time under no span is ``none``.  ``group_idle_s`` is
    the idle time while some ``serve.group`` is open."""
    from bench import trace

    lo, hi = trace._marks(planes)
    ops = trace.device_ops(planes, n_devices)[0]
    busy = trace.union(trace._clip([(t, t + d) for _, t, d in ops], lo, hi))
    idle, edge = [], lo
    for a, b in busy:
        if a > edge:
            idle.append((edge, a))
        edge = max(edge, b)
    if edge < hi:
        idle.append((edge, hi))
    spans = sorted(s for s in spans if s[0] < hi and s[1] > lo)
    points = sorted(
        {lo, hi}
        | {x for iv in idle for x in iv}
        | {x for s in spans for x in s[:2] if lo < x < hi}
    )
    gaps: dict[str, float] = {}
    group_idle = 0.0
    active: list = []
    nxt = k = 0
    for a, b in zip(points, points[1:]):
        while nxt < len(spans) and spans[nxt][0] <= a:
            active.append(spans[nxt])
            nxt += 1
        active = [s for s in active if s[1] > a]
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        if k == len(idle) or idle[k][0] > a:
            continue  # the device is busy here
        name = max(active, key=lambda s: (s[0], -s[1]))[2] if active else "none"
        gaps[name] = gaps.get(name, 0.0) + (b - a)
        if any(s[2] == "serve.group" for s in active):
            group_idle += b - a
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum(b - a for a, b in idle) * 1e-9,
        "gaps": {n: v * 1e-9 for n, v in sorted(gaps.items(), key=lambda kv: -kv[1])},
        "group_idle_s": group_idle * 1e-9,
    }


def gaps_line(swept: dict) -> str:
    parts = ", ".join(f"{n} {v:.4f} s" for n, v in swept["gaps"].items())
    return (
        f"program gaps: {parts}; idle {swept['idle_s']:.4f} s of {swept['window_s']:.4f} s, "
        f"{swept['group_idle_s']:.4f} s of it while a serve.group is open"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for path in (ROOT, ROOT / "src"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    from bench import run as bench_run

    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except (bench_run.NoChip, FileNotFoundError, KeyError) as e:
        bench_run.log(f"FAIL: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


def run(workload: str, seed: int, seconds: float, traced: bool, *, root: Path = ROOT) -> dict:
    """``bench.run.run`` with the program's tracer on from before the
    build; returns its result with the ``program`` readings added."""
    from bench import run as bench_run, trace
    from bench.catalog import Catalog
    from repro import telemetry

    telemetry.enable()
    try:
        result = bench_run.run(workload, seed, seconds, traced, root=root)
    finally:
        records = telemetry.disable()
    got = readings(records)
    if traced:
        # the directory bench/run.py records the traced window's profile in
        planes = trace.load(root / ".bench_run" / "trace" / workload)
        spans = on_trace_clock(window_spans(records), planes)
        swept = sweep(planes, spans, Catalog(root).cell(workload)["chips"])
        bench_run.log(gaps_line(swept))
        got["device_wait_on_host_pct"] = 100.0 * swept["group_idle_s"] / swept["window_s"]
        got["gaps"] = swept
    result["program"] = got
    return result


if __name__ == "__main__":
    sys.exit(main())
