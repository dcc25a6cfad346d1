"""Device time of the gather-fused filter kernel in the traced part of
the window, per request planned there."""

from __future__ import annotations

LAYER = "filter kernel"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "discover_p50_s"


from bench.metrics import kernel_seconds, traced


def read(run):
    seconds = kernel_seconds(run)
    plans = traced(run, "plan_query")
    return 1e3 * seconds / len(plans) if seconds and plans else None
