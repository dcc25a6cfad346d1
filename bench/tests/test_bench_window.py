"""The open loop and the arithmetic of the end-to-end metrics."""

from __future__ import annotations

import asyncio
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import window


def test_percentiles_and_rate_from_due_times():
    t0 = 100.0
    outs = [window.Outcome(due=t0 + i, sent=t0 + i, done=t0 + i + 0.1 * (i + 1)) for i in range(10)]
    outs.append(window.Outcome(due=t0 + 9.5, error="no answer"))
    lat = window.latencies(outs)
    assert lat == pytest.approx([0.1 * (i + 1) for i in range(10)])
    assert window.percentile(lat, 50) == pytest.approx(0.55)
    assert window.percentile(lat, 95) == pytest.approx(0.955)
    # the tenth answers at t0 + 10.0: inside a 10 s window, after a 9.9 s one
    assert window.completed_rate(outs, t0, 10.0) == pytest.approx(10 / 10.0)
    assert window.completed_rate(outs, t0, 9.9) == pytest.approx(9 / 9.9)
    assert window.lateness(outs) == pytest.approx([0.0] * 10)


class Engine:
    """Answers at once; one query stalls the event loop, as a long
    synchronous group does, and one may never answer."""

    def __init__(self, stall_on=None, stall=0.0, hang_on=None):
        self.stall_on, self.stall, self.hang_on = stall_on, stall, hang_on

    async def discover_async(self, query, q_cols):
        if query == self.hang_on:
            await asyncio.Event().wait()
        if query == self.stall_on:
            time.sleep(self.stall)
        await asyncio.sleep(0)
        return SimpleNamespace(results=[query])


def _run(engine, n=10, gap=0.05, seconds=0.5, grace=1.0):
    dues = [gap * i for i in range(n)]
    queries = [(i, [0]) for i in range(n)]
    outs, t0 = asyncio.run(window.drive(engine, dues, queries, seconds, grace=grace))
    return outs, t0, seconds


def test_a_stall_moves_the_tail_and_the_rate():
    outs, t0, s = _run(Engine())
    lat = window.latencies(outs)
    assert [o.entries for o in outs] == [[i] for i in range(10)]
    assert window.percentile(lat, 95) < 0.05
    assert window.completed_rate(outs, t0, s) >= 18
    stalled, t0, s = _run(Engine(stall_on=5, stall=0.3))
    lat = window.latencies(stalled)
    # requests due during the stall are sent late, and their latency counts it
    assert window.percentile(lat, 95) > 0.2
    assert window.lateness(stalled).max() > 0.2
    assert window.completed_rate(stalled, t0, s) <= 12


def test_a_request_without_answer_is_missing():
    outs, _, _ = _run(Engine(hang_on=3), grace=0.2)
    assert [o.error is None for o in outs].count(False) == 1
    assert "no answer" in outs[3].error
    assert np.isnan(outs[3].done)
