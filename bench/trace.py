"""The reduction from a profiler trace to device busy time, kernel time and
the breakdown the result line carries.

A traced run records part of its window with ``jax.profiler``; ``start``
and ``stop`` bracket it with two host marks, so the traced window is the
time between them on the trace's own clock.  A device's busy time is the
union of the intervals in which one of its operations ran; the device's
idle gaps are named by the innermost benchmark span (``bench.<name>``,
see ``bench/spans.py``) open at each gap's midpoint, or ``host`` where
none was.
"""

from __future__ import annotations

import glob
import json
import re
import time
from pathlib import Path

OPEN, CLOSE = "bench.trace_open", "bench.trace_close"
# the line of a device plane that holds one event per operation executed
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
TOP = 10


def start(log_dir: Path) -> float:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # Python call tracing would swamp the host
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    with jax.profiler.TraceAnnotation(OPEN):
        pass
    return time.perf_counter()


def stop() -> float:
    import jax

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(CLOSE):
        pass
    jax.profiler.stop_trace()
    return t


def load(log_dir: Path) -> list[dict]:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain planes."""
    import jax

    paths = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    return planes_of(jax.profiler.ProfileData.from_file(paths[-1]))


def planes_of(data) -> list[dict]:
    """``[{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]``.
    An operation's event is named by its HLO text; only the instruction's
    name (``%gather_filter_table_counts.1 = ...`` -> the part before `` = ``)
    is kept."""
    return [
        {
            "name": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": [
                        (e.name.split(" = ", 1)[0].lstrip("%"), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ],
                }
                for line in plane.lines
            ],
        }
        for plane in data.planes
    ]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _marks(planes: list[dict]) -> tuple[float, float]:
    found: dict[str, float] = {}
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, t, _ in line["events"]:
                if name in (OPEN, CLOSE):
                    found[name] = t
    if OPEN not in found or CLOSE not in found:
        raise ValueError("the trace holds no window marks")
    return found[OPEN], found[CLOSE]


def _host_spans(planes: list[dict]) -> list[tuple[float, float, str]]:
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, t, d in line["events"]:
                if name.startswith("bench.") and name not in (OPEN, CLOSE):
                    out.append((t, t + d, name[len("bench."):]))
    return out


def device_ops(planes: list[dict], n_devices: int) -> list[list[tuple[str, float, float]]]:
    """Per device (by id, the first ``n_devices``): its operation events."""
    found: dict[int, list] = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        ops = [line for line in plane["lines"] if line["name"] == OPS_LINE]
        found[int(m.group(1))] = [e for line in ops for e in line["events"]]
    ids = sorted(found)[:n_devices]
    if not ids:
        raise ValueError("the trace holds no device plane")
    return [found[i] for i in ids]


def reduce(planes: list[dict], n_devices: int = 1) -> dict:
    lo, hi = _marks(planes)
    window_ns = hi - lo
    per_device = device_ops(planes, n_devices)
    busy = []
    op_time: dict[str, float] = {}
    for ops in per_device:
        spans = union(_clip([(t, t + d) for _, t, d in ops], lo, hi))
        busy.append(sum(b - a for a, b in spans))
        for name, t, d in ops:
            if lo <= t < hi:
                op_time[name] = op_time.get(name, 0.0) + d
    # gaps of the first device, named by the host span open in each
    spans0 = union(_clip([(t, t + d) for _, t, d in per_device[0]], lo, hi))
    edges = [lo] + [x for s in spans0 for x in s] + [hi]
    host = _host_spans(planes)
    gap_time: dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        open_ = [s for s in host if s[0] <= mid < s[1]]
        # the innermost open span: the latest to start, the shortest on a tie
        name = max(open_, key=lambda s: (s[0], -s[1]))[2] if open_ else "host"
        gap_time[name] = gap_time.get(name, 0.0) + (b - a)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "op_seconds": {k: v * 1e-9 for k, v in op_time.items()},
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in top_ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in top_gaps],
        },
    }


def peaks(device_kind: str, root: Path) -> dict:
    """The published peaks of ``device_kind`` from ``bench/peaks.json``."""
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table["devices"][device_kind]
