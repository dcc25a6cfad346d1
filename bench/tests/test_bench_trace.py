"""The trace reduction, the peaks table, the needed-work function and the
per-layer readers, on a hand-built trace in the TPU's layout."""

from __future__ import annotations

import types
from pathlib import Path

import pytest

from bench import trace
from bench.catalog import Catalog
from bench.run import Run
from bench.spans import Spans
from bench.work import gather_filter

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
MS = 1e-3


@pytest.fixture(scope="module")
def planes():
    import jax

    text = (DATA / "tpu_v5e_trace.pbtxt").read_text()
    return trace.planes_of(jax.profiler.ProfileData.from_text_proto(text))


def test_reduce_busy_window_and_breakdown(planes):
    got = trace.reduce(planes)
    assert got["window_s"] == pytest.approx(13 * MS)
    # three operations inside the marks; the one before the open mark and the
    # module events are not counted
    assert got["busy_s"] == pytest.approx(1.7 * MS)
    assert got["op_seconds"] == pytest.approx(
        {"gather_filter_table_counts.1": 1.0 * MS, "fusion.1": 0.2 * MS, "fusion.3": 0.5 * MS}
    )
    assert got["breakdown"]["device_ops"][0] == ["gather_filter_table_counts.1", pytest.approx(1.0 * MS)]
    gaps = dict(got["breakdown"]["idle_gaps"])
    # each gap is named by the innermost span open at its midpoint
    assert gaps == pytest.approx(
        {"host": 6.5 * MS, "plan_query": 3.5 * MS, "score_from_counts": 1.2 * MS, "filter_launch": 0.1 * MS}
    )


def test_union():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_reduce_needs_marks_and_a_device(planes):
    with pytest.raises(ValueError, match="marks"):
        trace.reduce([p for p in planes if not p["name"].startswith("/host")])
    with pytest.raises(ValueError, match="device plane"):
        trace.reduce([p for p in planes if not p["name"].startswith("/device:TPU")])


def test_peaks_by_device_kind():
    v5e = trace.peaks("TPU v5 lite", ROOT)
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="TPU v9"):
        trace.peaks("TPU v9", ROOT)


def test_needed_bytes_hand_counted():
    # 3 distinct rows of 16 lanes, 5 items, 2 keys, 4 tables:
    # 3*16*4 + 5*(4+4+4) + 2*16*4 + 4*4
    assert gather_filter.needed_bytes(3, 5, 2, 16, 4) == 192 + 60 + 128 + 16
    assert gather_filter.needed_bytes(0, 0, 0, 4, 0) == 0
    assert gather_filter.roofline_seconds(819, {"hbm_bytes_per_s": 819e9}) == pytest.approx(1e-9)


def test_spans_wrap_and_restore():
    mod = types.SimpleNamespace(f=lambda x: x * 2)
    spans = Spans()
    spans.wrap(mod, "f", "double", before=lambda x: {"x": x}, after=lambda out: {"out": out})
    assert mod.f(3) == 6
    (t0, t1, info), = spans.of("double")
    assert t1 >= t0 and info == {"x": 3, "out": 6}
    spans.restore()
    assert not hasattr(mod.f, "__wrapped__")


def test_trace_readers(planes):
    """The device-trace metrics on the fixture: one launch of 2000 items
    (1500 distinct rows) against 100 keys over 300 tables at 16 lanes."""
    cat = Catalog(ROOT)
    run = Run(cell={}, config={}, seconds=1.0)
    run.trace = trace.reduce(planes)
    run.spans = Spans()
    run.spans.records = {
        "plan_query": [(1.0, 1.1, {"items": 2000})],
        "filter_launch": [(1.2, 1.3, {"items": 2000, "distinct_rows": 1500, "keys": 100, "lanes": 16, "tables": 300})],
    }
    run.trace_bounds = (0.5, 2.0)
    run.peaks = trace.peaks("TPU v5 lite", ROOT)
    run.work = gather_filter
    read = lambda name: cat.module("metrics", name).read(run)  # noqa: E731
    assert read("filter_kernel_ms") == pytest.approx(1.0)
    need = gather_filter.needed_bytes(1500, 2000, 100, 16, 300)
    assert read("filter_roofline_pct") == pytest.approx(100 * need / 819e9 / 1e-3)
    assert 0 < read("filter_roofline_pct") <= 100
    assert read("device_idle_pct") == pytest.approx(100 * (1 - 1.7 / 13))
    run.trace_bounds = None  # no launch was traced: nothing to read
    assert read("filter_kernel_ms") is None and read("filter_roofline_pct") is None
