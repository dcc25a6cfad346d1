"""The readings of the program's own telemetry and the device-idle sweep,
on hand-built records and a hand-built trace; and the per-layer readers of
the program's session and build counters."""

from __future__ import annotations

from pathlib import Path

import pytest

from bench import program, trace
from bench.catalog import Catalog
from bench.run import Run
from repro.core.index import BuildStats
from repro.core.session import SessionStats
from repro.telemetry import Span

ROOT = Path(__file__).resolve().parents[2]
MS = 1e6  # trace times are in ns


def _records():
    """Set-up (a build and a warm-up plan) and one served request:
    times in ns, 1 ms = 1e6."""
    r = []

    def add(sid, parent, name, t0, t1, **attrs):
        r.append(Span(sid, parent, name, int(t0 * MS), int(t1 * MS), attrs))

    add(1, None, "jit.lower", 0, 2000, seconds=2.0)  # set-up
    add(2, None, "plan.query", 2000, 2100, rid=None, items=99, tables=9)  # warm-up
    add(3, None, "serve.wait", 3000, 3500)
    add(4, None, "serve.pump_turn", 3500, 4400, backlog=1, groups=1)
    add(5, 4, "serve.group", 3500, 4400, size=1, rids=[0], waits=[0.02])
    add(6, 5, "plan.query", 3500, 3560, rid=0, items=1000, tables=40)
    add(7, 5, "filter.assemble", 3560, 3570)
    add(8, 5, "filter.launch", 3570, 3630, h2d_bytes=5000, elig_bytes=4000)
    add(9, 8, "filter.pad", 3570, 3575)
    add(10, 8, "filter.readback", 3575, 3630)
    add(11, 5, "score.request", 3630, 4380, rid=0)
    add(12, 11, "score.rank", 3630, 3680)
    add(13, 11, "score.tables", 3680, 4370, regather_s=0.1, exact_s=0.55,
        pairs_verified=700, tables_pruned=30, tables_verified=10)
    add(14, 6, "jit.lower", 3510, 3520, seconds=0.01)
    add(15, None, "serve.wait", 4400, 5000)
    return r


def test_readings_of_the_window():
    got = program.readings(_records())
    assert got["verify_exact_ms"] == pytest.approx(550)
    assert got["verify_regather_ms"] == pytest.approx(100)
    assert got["rank_ms"] == pytest.approx(50)
    assert got["pairs_verified"] == 700
    # the warm-up plan (9 tables) is not the window's
    assert got["rule_pruned_share"] == pytest.approx(30 / 40)
    assert got["h2d_bytes"] == 5000 and got["elig_bytes"] == 4000
    assert got["pump_turn_ms"] == pytest.approx(900)
    assert got["plan_query_ms"] == pytest.approx(60)
    assert got["launch_ms"] == pytest.approx(60)
    assert got["score_request_ms"] == pytest.approx(750)
    assert got["queue_wait_ms"] == pytest.approx(20)
    assert got["score_children_share"] == pytest.approx(740 / 750)
    # two waits, the turn, and the ten records under it
    assert got["spans_per_request"] == 13


def test_readings_leave_out_what_is_not_there():
    assert program.readings([]) == {}
    set_up = [r for r in _records() if r.parent is None and not r.name.startswith("serve.")]
    assert program.readings(set_up) == {}


def _planes(host, ops):
    """A host plane with the window marks at 0 and 100 ms and ``host``
    spans; a device plane with ``ops``; all (name, start_ms, dur_ms)."""
    ev = lambda items: [(n, t * MS, d * MS) for n, t, d in items]  # noqa: E731
    marks = [(trace.OPEN, 0, 0), (trace.CLOSE, 100, 0)]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": ev(marks + host)}]},
        {"name": "/device:TPU:0", "lines": [{"name": trace.OPS_LINE, "events": ev(ops)}]},
    ]


OPS = [("fusion.1", 10, 10), ("gather_filter_table_counts.1", 90, 5)]


def test_sweep_splits_a_gap_among_the_spans_it_crosses():
    # one idle gap, 20-90 ms, crosses score.tables (20-50), the rest of the
    # group (50-80) and the pump's sleep (80-100)
    host = [("mate.serve.group", 5, 75), ("mate.score.tables", 20, 30), ("mate.serve.wait", 80, 20)]
    planes = _planes(host, OPS)
    got = program.sweep(planes, program.annotations(planes))
    assert got["window_s"] == pytest.approx(0.1)
    assert got["idle_s"] == pytest.approx(0.085)
    assert got["gaps"] == pytest.approx(
        {"serve.group": 0.035, "score.tables": 0.030, "serve.wait": 0.015, "none": 0.005}
    )
    assert list(got["gaps"])[0] == "serve.group"  # largest first
    assert got["group_idle_s"] == pytest.approx(0.065)
    assert "score.tables 0.0300 s" in program.gaps_line(got)
    # the midpoint rule books the whole 70 ms gap to the span open at 55 ms
    # (and the 0-10 ms gap to the group open at 5 ms)
    midpoint = trace.reduce(_planes([(n.replace("mate.", "bench."), t, d) for n, t, d in host], OPS))
    assert dict(midpoint["breakdown"]["idle_gaps"])["serve.group"] == pytest.approx(0.070 + 0.010)
    assert "score.tables" not in dict(midpoint["breakdown"]["idle_gaps"])


def test_sweep_innermost_and_clipping():
    # a span that starts before the window and one that ends after it; the
    # later-starting of two open spans takes the time
    host = [("mate.serve.wait", -50, 60), ("mate.serve.group", 30, 200), ("mate.plan.query", 40, 10)]
    planes = _planes(host, [("fusion.1", 60, 20)])
    got = program.sweep(planes, program.annotations(planes))
    assert got["gaps"] == pytest.approx(
        {"serve.wait": 0.010, "none": 0.020, "serve.group": 0.040, "plan.query": 0.010}
    )
    assert got["group_idle_s"] == pytest.approx(0.050)
    # no span at all: every idle instant is booked to none
    bare = program.sweep(_planes([], OPS), [])
    assert bare["gaps"] == pytest.approx({"none": 0.085}) and bare["group_idle_s"] == 0


def test_records_are_placed_on_the_trace_clock():
    """The profiler kept the annotations of two spans but dropped the wait
    that was open when it started; the records place all three, at the
    offset the kept annotations agree on."""
    off = 7_000 * MS  # trace clock = record clock + 7 s
    recs = [
        Span(1, None, "serve.wait", int(-30 * MS), int(12 * MS)),
        Span(2, None, "serve.group", int(12 * MS), int(70 * MS)),
        Span(3, 2, "score.tables", int(20 * MS), int(50 * MS)),
        Span(4, None, "serve.group", int(900 * MS), int(950 * MS)),  # outside the trace
    ]
    host = [("mate.serve.group", 12 + 7000, 58), ("mate.score.tables", 20 + 7000 + 0.001, 30)]
    planes = _planes(host, OPS)
    placed = program.on_trace_clock(recs, planes)
    assert placed[0] == pytest.approx((-30 * MS + off, 12 * MS + off, "serve.wait"))
    assert [p[2] for p in placed] == ["serve.wait", "serve.group", "score.tables", "serve.group"]
    assert placed[2][0] - placed[1][0] == pytest.approx(8 * MS, abs=2e3)
    assert program.on_trace_clock(recs, _planes([], OPS)) == []


def _run(stats=None, build_stats=None):
    run = Run(cell={}, config={}, seconds=1.0)
    run.stats, run.build_stats = stats, build_stats
    return run


def test_counter_readers():
    cat = Catalog(ROOT)
    read = lambda name, run: cat.module("metrics", name).read(run)  # noqa: E731
    st = SessionStats(
        requests=4, verified_tp=30, verified_fp=10, tables_fetched=100,
        tables_gated=20, tables_pruned_rule1=50, tables_pruned_rule2=10,
    )
    run = _run(st, BuildStats(corpus_seconds=2.5))
    assert read("pairs_verified", run) == 10
    assert read("rule_pruned_share", run) == pytest.approx(60 / 80)
    assert read("build_corpus_s", run) == 2.5
    # nothing served, no build: nothing to read
    assert read("pairs_verified", _run(SessionStats())) is None
    assert read("rule_pruned_share", _run(SessionStats())) is None
    assert read("build_corpus_s", _run()) is None


def test_counter_readers_on_a_program_without_the_counters():
    """A program whose stats lack the table totals and the corpus clock (as
    before they were added) gives nothing to read, and raises nothing."""
    cat = Catalog(ROOT)
    older_stats = type("S", (), {"requests": 2, "verified_tp": 1, "verified_fp": 1, "tables_gated": 0})()
    older_build = type("B", (), {"hash_seconds": 1.0})()
    run = _run(older_stats, older_build)
    assert cat.module("metrics", "rule_pruned_share").read(run) is None
    assert cat.module("metrics", "build_corpus_s").read(run) is None
    assert cat.module("metrics", "pairs_verified").read(run) == 1
