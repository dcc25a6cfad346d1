"""Programs JAX lowered (compiled, or fetched from the persistent cache)
that started inside the window; the warm-up should leave none."""

from __future__ import annotations

LAYER = "filter launch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "discover_p50_s"


def read(run):
    return run.compiles_between(run.t0, run.t_end)
