"""Mean host time of ``core.batched.plan_query`` per request: init column,
key hashing, candidate gather, profile gate and eligibility."""

from __future__ import annotations

LAYER = "planning"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "discover_p50_s"


def read(run):
    spans = run.spans.of("plan_query")
    return 1e3 * sum(b - a for a, b, _ in spans) / len(spans) if spans else None
