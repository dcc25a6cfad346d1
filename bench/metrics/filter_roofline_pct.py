"""The gather-fused filter kernel's share of its HBM-bandwidth roofline in
the traced part of the window: the bytes its launches need
(``bench/work/gather_filter.py``) at the chip's peak bandwidth, over the
kernel's device time."""

from __future__ import annotations

LAYER = "filter kernel"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "discover_p50_s"


from bench.metrics import kernel_seconds, traced


def read(run):
    seconds = kernel_seconds(run)
    launches = traced(run, "filter_launch")
    if not seconds or not launches:
        return None
    need = sum(
        run.work.needed_bytes(i["distinct_rows"], i["items"], i["keys"], i["lanes"], i["tables"])
        for _, _, i in launches
    )
    return 100.0 * run.work.roofline_seconds(need, run.peaks) / seconds
