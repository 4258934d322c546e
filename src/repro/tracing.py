"""In-program spans and a compile counter for the serving path.

Off by default. An operator (or a benchmark) turns it on around the window
it wants to see and drains the records afterwards::

    from repro import tracing
    tracing.enable()
    ...serve...
    tracing.disable()
    records, dropped = tracing.drain(), tracing.dropped()
    compiles = tracing.compiles()

Off, a span site costs one test of a module-level boolean: :func:`span`
returns one shared no-op context manager and nothing is allocated or
recorded. On, each span

* is also a ``jax.profiler.TraceAnnotation``, so a running profiler writes
  it into the trace's host plane next to the device's operations;
* appends one :class:`Record` to an in-memory list capped at :data:`CAP`
  records (records past the cap are counted by :func:`dropped`, never
  silently lost). Its ``start_ns``/``end_ns`` are ``time.time_ns()``, the
  wall clock the profiler stamps host events with, so records line up with
  the trace without the annotations (``lane.queue`` spans two threads and
  exists only as a record).

A span knows its parent and its request through a ``contextvars`` context:
``span(..., req=<id>)`` names the request for everything opened inside it,
:func:`in_lane` carries the context into an executor's worker lane, and
:func:`current` hands it to code that runs on a runtime thread (the body
of an ``io_callback``), which passes it back as ``span(..., ctx=...)``.

:func:`compiles` counts XLA compilations (``backend_compile`` events,
persistent-cache loads included) while tracing is on, through one
``jax.monitoring`` listener registered on the first :func:`enable`. The
switch, the records and the counts are process-wide, so tests turn tracing
off and drain it when they finish. The module depends on the standard
library and ``jax`` alone.
"""
from __future__ import annotations

import contextvars
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

# records kept per enable/drain cycle; past it records are counted, not kept
CAP = 1_000_000
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record(NamedTuple):
    """One closed span. Times are ``time.time_ns()`` (wall clock, ns);
    ``cpu_ns`` is the thread CPU time spent between enter and exit."""
    name: str
    start_ns: int
    end_ns: int
    cpu_ns: int
    thread: int
    span_id: int
    parent: Optional[int]
    req: Optional[int]
    attrs: dict


_on = False
_records: list[Record] = []
_dropped = 0
_compiles = 0
_listening = False
_lock = threading.Lock()
_ids = itertools.count(1)
# (request id, id of the innermost open span) of the calling context
_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "repro_tracing_ctx", default=(None, None))


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def _keep(rec: Record) -> None:
    global _dropped
    with _lock:
        if len(_records) < CAP:
            _records.append(rec)
        else:
            _dropped += 1


class _Span:
    __slots__ = ("name", "attrs", "req", "parent", "span_id", "ann", "token",
                 "t0", "c0")

    def __init__(self, name: str, req, ctx, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.req, self.parent = ctx if ctx is not None else _ctx.get()
        if req is not None:
            self.req = req

    def __enter__(self):
        self.span_id = next(_ids)
        self.token = _ctx.set((self.req, self.span_id))
        self.c0 = time.thread_time_ns()
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        self.ann.__exit__(*exc)
        c1 = time.thread_time_ns()
        _ctx.reset(self.token)
        _keep(Record(self.name, self.t0, t1, c1 - self.c0,
                     threading.get_ident(), self.span_id, self.parent,
                     self.req, self.attrs))
        return False


def span(name: str, *, req: Optional[int] = None, ctx: Optional[tuple] = None,
         **attrs):
    """Context manager timing one layer's work.

    Args:
        name: ``<layer>.<step>``, e.g. ``store.gather``.
        req: request id for this span and every span opened inside it
            (default: the enclosing span's).
        ctx: ``(req, parent span id)`` from :func:`current`, for code on a
            thread that does not inherit the caller's context.
        **attrs: small values kept on the record (e.g. ``kind="device"``).
    """
    if not _on:
        return _NOOP
    return _Span(name, req, ctx, attrs)


def current() -> Optional[tuple]:
    """``(req, innermost open span id)`` of the calling context, or ``None``
    when tracing is off; pass it to ``span(ctx=...)`` on another thread."""
    return _ctx.get() if _on else None


class _Lane:
    __slots__ = ("fn", "ctx", "t0")

    def __init__(self, fn):
        self.fn = fn
        self.ctx = contextvars.copy_context()
        self.t0 = time.time_ns()

    def __call__(self, *args, **kwargs):
        return self.ctx.run(self._run, *args, **kwargs)

    def _run(self, *args, **kwargs):
        req, parent = _ctx.get()
        _keep(Record("lane.queue", self.t0, time.time_ns(), 0,
                     threading.get_ident(), next(_ids), parent, req, {}))
        return self.fn(*args, **kwargs)


def in_lane(fn):
    """``fn`` to hand to a worker lane: unchanged when tracing is off;
    otherwise run in a copy of the submitting context, recording the wait
    from now to its start as ``lane.queue``."""
    return _Lane(fn) if _on else fn


def _on_compile(event: str, *_args, **_kwargs) -> None:
    global _compiles
    if _on and event == COMPILE_EVENT:
        with _lock:
            _compiles += 1


def enable() -> None:
    """Start recording spans and counting compilations."""
    global _on, _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile)
            _listening = True
        _on = True


def disable() -> None:
    """Stop recording; records and counts stay until :func:`drain`."""
    global _on
    _on = False


def enabled() -> bool:
    """Whether spans are being recorded."""
    return _on


def drain() -> list[Record]:
    """Hand out the records kept so far and start a fresh list; also
    zeroes :func:`dropped` and :func:`compiles`."""
    global _records, _dropped, _compiles
    with _lock:
        out, _records = _records, []
        _dropped = _compiles = 0
    return out


def dropped() -> int:
    """Records lost to the cap since the last :func:`drain`."""
    return _dropped


def compiles() -> int:
    """Compilations counted while tracing was on, since the last
    :func:`drain`."""
    return _compiles
