"""Eligible (row, key) pairs the phase-B re-gather tested on the host, mean
per request (``SessionStats.regather_pairs``); read against the pairs the
filter launch tested (``filter_checks``).  A program without the counter
gives nothing to read."""

from __future__ import annotations

LAYER = "verification and ranking"
UNIT = "pairs/req"
SOURCE = "program_counter"
MOVES = "discover_p50_s"


def read(run):
    st = run.stats
    pairs = getattr(st, "regather_pairs", None)
    if pairs is None or not st.requests:
        return None
    return pairs / st.requests
