"""Seconds of the offline build's super-key, posting-list and merge passes
(``BuildStats`` superkey + postings + merge seconds)."""

from __future__ import annotations

LAYER = "offline build"
UNIT = "s"
SOURCE = "program_span"
MOVES = "build_s"


def read(run):
    b = run.build_stats
    return b.superkey_seconds + b.postings_seconds + b.merge_seconds if b else None
