"""Mean host time of ``core.batched.score_from_counts`` per request:
rule-1/2 pruning, exact verification, the heap and the ranking launch."""

from __future__ import annotations

LAYER = "verification and ranking"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "discover_p50_s"


def read(run):
    spans = run.spans.of("score_from_counts")
    return 1e3 * sum(b - a for a, b, _ in spans) / len(spans) if spans else None
