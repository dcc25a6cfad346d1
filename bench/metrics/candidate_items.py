"""Mean posting-list items per request after the profile gate: the
candidate block the filter launch carries (exact count)."""

from __future__ import annotations

LAYER = "planning"
UNIT = "items/req"
SOURCE = "program_counter"
MOVES = "discover_p50_s"


def read(run):
    spans = run.spans.of("plan_query")
    return sum(info["items"] for _, _, info in spans) / len(spans) if spans else None
