"""Host time of the filter launch (``kernels.ops.filter_hits_table_counts``
as ``plan_and_count`` calls it) per request: eligibility upload, the
kernel and the counts readback."""

from __future__ import annotations

LAYER = "filter launch"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "discover_p50_s"


def read(run):
    launches = run.spans.of("filter_launch")
    plans = run.spans.of("plan_query")
    if not launches or not plans:
        return None
    return 1e3 * sum(b - a for a, b, _ in launches) / len(plans)
