"""Host spans around the program's layer entry points, for the traced run.

The benchmark records them from its own files: it swaps a module-level
function (or a class's method) for a wrapper that keeps a clock and opens a
``jax.profiler.TraceAnnotation`` named ``bench.<span>``, so the profiler's
trace shows what the host was doing in each device gap.  Nothing is wrapped
in an untraced run, so the end-to-end metrics carry no instrumentation.
"""

from __future__ import annotations

import functools
import time


class Spans:
    def __init__(self):
        self.records: dict[str, list[tuple[float, float, dict]]] = {}
        self._undo: list[tuple[object, str, object]] = []

    def wrap(self, owner, attr: str, name: str, before=None, after=None) -> None:
        """Time every call of ``owner.attr``; ``before(*args, **kwargs)`` and
        ``after(result)`` return dicts kept with the span."""
        import jax

        orig = getattr(owner, attr)
        recs = self.records.setdefault(name, [])
        label = f"bench.{name}"

        @functools.wraps(orig)
        def wrapper(*args, **kwargs):
            info = before(*args, **kwargs) if before else {}
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = orig(*args, **kwargs)
            t1 = time.perf_counter()
            if after:
                info.update(after(out))
            recs.append((t0, t1, info))
            return out

        setattr(owner, attr, wrapper)
        self._undo.append((owner, attr, orig))

    def restore(self) -> None:
        while self._undo:
            owner, attr, orig = self._undo.pop()
            setattr(owner, attr, orig)

    def of(self, name: str, lo: float = float("-inf"), hi: float = float("inf")):
        """Spans of ``name`` that started in [lo, hi]."""
        return [r for r in self.records.get(name, []) if lo <= r[0] <= hi]


def instrument(spans: Spans) -> None:
    """Wrap the serving tier, planning, the filter launch and scoring."""
    import numpy as np

    from repro.core import batched
    from repro.kernels import ops
    from repro.serve import engine

    def group_waits(self, group):
        now = self.clock()
        return {"waits": [now - r.arrival for r in group if not r.future.cancelled()]}

    def launch_shape(row_sk, query_sk, elig, seg_ids, n_tables, **kwargs):
        rows = kwargs.get("rows")
        rows = np.asarray(rows if rows is not None else np.zeros(0))
        return {
            "items": int(rows.shape[0] if row_sk is None else row_sk.shape[0]),
            "distinct_rows": int(np.unique(rows).size) if row_sk is None else int(row_sk.shape[0]),
            "keys": int(query_sk.shape[0]),
            "lanes": int(query_sk.shape[1]),
            "tables": int(n_tables),
        }

    spans.wrap(engine.DiscoveryEngine, "_serve_group", "serve_group", before=group_waits)
    spans.wrap(batched, "plan_query", "plan_query", after=lambda p: {"items": p.block.n_items})
    spans.wrap(ops, "filter_hits_table_counts", "filter_launch", before=launch_shape)
    spans.wrap(batched, "score_from_counts", "score_from_counts")
