"""Mean wait of a request in the serving queue, from submit to the start
of the group that serves it (``DiscoveryEngine._serve_group`` span)."""

from __future__ import annotations

LAYER = "serving tier"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "discover_p50_s"


def read(run):
    waits = [w for _, _, info in run.spans.of("serve_group") for w in info["waits"]]
    return 1e3 * sum(waits) / len(waits) if waits else None
