"""Share of the candidate tables left after the profile gate that rule 1 or
rule 2 pruned before verification, over every request served
(``SessionStats.tables_pruned_rule1 + tables_pruned_rule2`` over
``tables_fetched - tables_gated``).  A program whose session stats do not
sum the table counters gives nothing to read."""

from __future__ import annotations

LAYER = "verification and ranking"
UNIT = "frac"
SOURCE = "program_counter"
MOVES = "discover_p50_s"


def read(run):
    st = run.stats
    if st is None or not hasattr(st, "tables_pruned_rule1"):
        return None
    candidates = st.tables_fetched - st.tables_gated
    if candidates <= 0:
        return None
    return (st.tables_pruned_rule1 + st.tables_pruned_rule2) / candidates
