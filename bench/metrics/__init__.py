"""Per-layer metrics: ``bench/metrics/<name>.py`` reads one metric from a
traced run (``bench.run.Run``) and returns None when it finds nothing to
read.  Helpers shared by the trace-based readers live here."""

from __future__ import annotations

# the filter kernel's operations in the device trace
FILTER_KERNEL = "gather_filter_table_counts"


def traced(run, span: str):
    """Spans of ``span`` that started inside the traced part of the window."""
    if run.trace_bounds is None:
        return []
    return run.spans.of(span, *run.trace_bounds)


def kernel_seconds(run) -> float:
    if not run.trace:
        return 0.0
    return sum(s for name, s in run.trace["op_seconds"].items() if FILTER_KERNEL in name)
