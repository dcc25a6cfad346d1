"""The measured window: an open loop of due requests through
``AsyncDiscoveryEngine``, and the arithmetic of its end-to-end metrics.

Each request is a client task that sleeps until its due time, submits,
and awaits its answer.  Its latency runs from the due time to the moment
the client resumes.  The engine serves a group synchronously on the event
loop, so a long group delays the clients due meanwhile: that delay is the
server's, and it counts.  How late each client actually submitted is kept
apart (``sent - due``) so a starved generator can be told from a slow
server.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Outcome:
    due: float  # absolute perf_counter seconds
    sent: float = float("nan")
    done: float = float("nan")
    entries: list | None = None
    error: str | None = None


async def drive(engine, dues, queries, seconds: float, *, grace: float, marks=()):
    """Offer ``queries[i]`` at ``t0 + dues[i]``; ``marks`` are (offset,
    callable) run on the loop at ``t0 + offset`` (the traced run's
    profiler start and stop).  Returns (outcomes, t0); requests not done
    ``grace`` seconds after the window's close are left with an error."""
    t0 = time.perf_counter() + 0.05
    out = [Outcome(due=t0 + d) for d in dues]

    async def client(i: int) -> None:
        rec = out[i]
        await asyncio.sleep(max(rec.due - time.perf_counter(), 0.0))
        rec.sent = time.perf_counter()
        query, q_cols = queries[i]
        try:
            req = await engine.discover_async(query, q_cols)
            rec.entries = req.results
        except Exception as e:  # a failed request is counted, not raised
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = time.perf_counter()

    async def mark(offset: float, fn) -> None:
        await asyncio.sleep(max(t0 + offset - time.perf_counter(), 0.0))
        fn()

    tasks = [asyncio.create_task(client(i)) for i in range(len(out))]
    tasks += [asyncio.create_task(mark(o, fn)) for o, fn in marks]
    _, pending = await asyncio.wait(
        tasks, timeout=max(t0 + seconds + grace - time.perf_counter(), 0.0)
    )
    for task in pending:
        task.cancel()
    for task in pending:
        try:
            await task
        except asyncio.CancelledError:
            pass
    for rec in out:
        if rec.entries is None and rec.error is None:
            rec.error = f"no answer {grace:.0f} s after the window closed"
    return out, t0


def latencies(outcomes: list[Outcome]) -> np.ndarray:
    """Seconds from due time to answer, for every answered request."""
    return np.array([o.done - o.due for o in outcomes if o.error is None])


def percentile(values: np.ndarray, q: float) -> float:
    """The q-th percentile by linear interpolation between order statistics."""
    return float(np.percentile(values, q))


def completed_rate(outcomes: list[Outcome], t0: float, seconds: float) -> float:
    """Requests answered inside the window, per second of window."""
    end = t0 + seconds
    return sum(1 for o in outcomes if o.error is None and o.done <= end) / seconds


def lateness(outcomes: list[Outcome]) -> np.ndarray:
    """Seconds each client submitted after its due time."""
    return np.array([o.sent - o.due for o in outcomes if o.sent == o.sent])
