"""Filter-surviving (row, key) pairs verified exactly on the host, mean per
request (``SessionStats.verified_tp + verified_fp``)."""

from __future__ import annotations

LAYER = "verification and ranking"
UNIT = "pairs/req"
SOURCE = "program_counter"
MOVES = "discover_p50_s"


def read(run):
    st = run.stats
    if st is None or not st.requests:
        return None
    return (st.verified_tp + st.verified_fp) / st.requests
