"""Seconds the lake's ``Corpus`` took to intern and encode its cells before
the index build (``BuildStats.corpus_seconds``, measured by the corpus).
A program whose build stats lack it gives nothing to read."""

from __future__ import annotations

LAYER = "offline build"
UNIT = "s"
SOURCE = "program_span"
MOVES = "build_s"


def read(run):
    return getattr(run.build_stats, "corpus_seconds", None)
