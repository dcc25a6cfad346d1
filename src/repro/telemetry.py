"""In-process spans and counters for the serving path.

Tracing is off by default.  ``enable()`` starts recording and ``disable()``
stops it and returns what was recorded; nothing else switches it (no
environment variable, no config field).  While it is off, ``span`` returns
one shared no-op context and ``count`` returns at once: no clock is read and
nothing is allocated.

While it is on:

  * ``with span(name, **attrs)`` keeps a ``Span`` record
    ``(sid, parent, name, t0_ns, t1_ns, attrs)`` on ``time.perf_counter_ns``.
    Spans nest; the innermost open span lives in a ``ContextVar``, so each
    asyncio task and each thread has its own.
  * ``count(key, n)`` adds ``n`` to ``key`` in the innermost open span's
    attrs.
  * Every span also opens ``jax.profiler.TraceAnnotation("mate.<name>")``,
    which puts it on the device trace's own clock when a profiler trace is
    running.
  * Each program lowering JAX reports is kept as a ``jit.lower`` record under
    the innermost open span, so the records show which layer of which
    request compiled.
  * ``begin``/``end`` record an interval from two timestamps.  It never
    becomes the parent of another span, so it may run across an ``await``
    (the serving pump's sleep); its profiler annotation still covers it.

There is no exporter: records stay in memory until ``disable()`` hands them
over.
"""

from __future__ import annotations

import contextvars
import dataclasses
import itertools
import time

# the event JAX records each time it lowers a program (a compile, or a fetch
# from the persistent cache)
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
PREFIX = "mate."


@dataclasses.dataclass
class Span:
    sid: int
    parent: int | None
    name: str
    t0_ns: int
    t1_ns: int = 0
    attrs: dict = dataclasses.field(default_factory=dict)


_on = False
_records: list[Span] = []
_ids = itertools.count(1)
_current: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "repro_telemetry_span", default=None
)
_listening = False


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NOOP = _Noop()


class _Open:
    __slots__ = ("span", "_token", "_annotation")

    def __init__(self, name: str, attrs: dict):
        import jax

        parent = _current.get()
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + name)
        self.span = Span(next(_ids), parent.sid if parent else None, name, 0, 0, attrs)

    def __enter__(self) -> Span:
        self._annotation.__enter__()
        self._token = _current.set(self.span)
        self.span.t0_ns = time.perf_counter_ns()
        return self.span

    def __exit__(self, *exc):
        self.span.t1_ns = time.perf_counter_ns()
        _current.reset(self._token)
        self._annotation.__exit__(*exc)
        if _on:
            _records.append(self.span)
        return False


def enabled() -> bool:
    return _on


def span(name: str, **attrs):
    """A context that records ``name`` while tracing is on; the shared no-op
    context otherwise.  ``with span(...) as s`` gives the ``Span`` (None when
    off), whose ``attrs`` a caller may fill in."""
    if not _on:
        return NOOP
    return _Open(name, attrs)


def count(key: str, n=1) -> None:
    """Add ``n`` to counter ``key`` of the innermost open span."""
    if not _on:
        return
    s = _current.get()
    if s is not None:
        s.attrs[key] = s.attrs.get(key, 0) + n


def begin(name: str):
    """Start an interval recorded from two timestamps; pass the token to
    ``end``.  None while tracing is off."""
    if not _on:
        return None
    import jax

    annotation = jax.profiler.TraceAnnotation(PREFIX + name)
    annotation.__enter__()
    parent = _current.get()
    return name, parent.sid if parent else None, time.perf_counter_ns(), annotation


def end(token, **attrs) -> None:
    if token is None:
        return
    name, parent, t0, annotation = token
    t1 = time.perf_counter_ns()
    annotation.__exit__(None, None, None)
    if _on:
        _records.append(Span(next(_ids), parent, name, t0, t1, attrs))


def _on_event(event: str, duration_secs: float, **kwargs) -> None:
    if not _on or event != LOWERING_EVENT:
        return
    t1 = time.perf_counter_ns()
    parent = _current.get()
    _records.append(
        Span(
            next(_ids), parent.sid if parent else None, "jit.lower",
            t1 - int(duration_secs * 1e9), t1, {"seconds": duration_secs},
        )
    )


def enable() -> None:
    """Start recording (a no-op while already on)."""
    global _on, _records, _listening
    if _on:
        return
    if not _listening:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True
    _records = []
    _on = True


def disable() -> list[Span]:
    """Stop recording and return the records kept since ``enable()``."""
    global _on, _records
    _on = False
    out, _records = _records, []
    return out

