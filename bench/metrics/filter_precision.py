"""Share of the filter's survivors that exact verification confirmed over
the window: verified_tp / (verified_tp + verified_fp) (exact counts)."""

from __future__ import annotations

LAYER = "verification and ranking"
UNIT = "frac"
SOURCE = "program_counter"
MOVES = "discover_p50_s"


def read(run):
    tp, fp = run.stats.verified_tp, run.stats.verified_fp
    return tp / (tp + fp) if tp + fp else None
