"""Seconds of the offline build's unique-value hash pass
(``BuildStats.hash_seconds``)."""

from __future__ import annotations

LAYER = "offline build"
UNIT = "s"
SOURCE = "program_span"
MOVES = "build_s"


def read(run):
    return run.build_stats.hash_seconds if run.build_stats else None
