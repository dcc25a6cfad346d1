"""The bytes the §6.3 gather-fused filter launch needs, whatever runs it.

One launch filters ``items`` posting-list items (candidate rows, with
repeats) of ``tables`` candidate tables against ``keys`` query super keys
of ``lanes`` uint32 lanes, and writes one int32 count per table.  The
algorithm has to read each distinct candidate row's super key once, each
item's row offset, table id and init-value index once (three int32), each
query key once, and write the counts.  What an implementation moves
besides (a whole 128-lane store line per row, a dense eligibility matrix,
padding) is its own cost and is not counted, so the share of the
bandwidth roofline cannot pass 100%.  The filter's compares have no
published vector-unit peak, so they bound nothing here.
"""

from __future__ import annotations


def needed_bytes(distinct_rows: int, items: int, keys: int, lanes: int, tables: int) -> int:
    return distinct_rows * lanes * 4 + items * 12 + keys * lanes * 4 + tables * 4


def roofline_seconds(nbytes: int, peaks: dict) -> float:
    """The least time the chip's HBM bandwidth allows for ``nbytes``."""
    return nbytes / peaks["hbm_bytes_per_s"]
