"""Spans and counters of the scheduler's walk.

A span times one phase on the host clock and adds the time to a field of
the :class:`repro.core.scheduler.WalkStats` of the call in progress; a
counter adds a count to one.  The walks make their ``WalkStats`` current
(:class:`scope`), so the placement backends and kernels add to it without
a ``WalkStats`` argument in the backend protocol.

While a profiler trace is being recorded (``jax.profiler.start_trace``),
every span is also a ``jax.profiler.TraceAnnotation`` on the profiler's
host clock, so a trace names each device idle gap by the walk phase that
covers it.  Span names start with ``sched.``; every span of one entry
call (:class:`call`) carries ``call=<n>``, a per-process call number.
With no trace recorded a span builds no annotation and formats no
arguments.

This module imports only the standard library: ``repro.core`` stays
importable without jax, and jax is looked up only once something has
imported it.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
import time

__all__ = ["call", "count", "launch", "note", "scope", "span"]

_STATS: contextvars.ContextVar = contextvars.ContextVar("repro_walk_stats", default=None)
_CALL: contextvars.ContextVar = contextvars.ContextVar("repro_call", default=None)
_OPEN: contextvars.ContextVar = contextvars.ContextVar("repro_open_span", default=None)
_CALLS = itertools.count(1)
_now = time.perf_counter
_annotation_cls = None


def _annotation():
    """``jax.profiler.TraceAnnotation`` while a trace is recorded, else None."""
    global _annotation_cls
    if _annotation_cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None
        _annotation_cls = profiler.TraceAnnotation
    return _annotation_cls if _annotation_cls.is_enabled() else None


class span:
    """Time a phase: add its host-clock microseconds to ``field`` of the
    current ``WalkStats`` (if any), and annotate the profiler's trace
    with ``name`` and ``args`` while one is recorded."""

    __slots__ = ("name", "field", "args", "_ann", "_tok", "_t0")

    def __init__(self, name: str, field: str | None = None, **args) -> None:
        self.name, self.field, self.args = name, field, args

    def __enter__(self) -> span:
        cls = _annotation()
        self._ann = None
        if cls is not None:
            n = _CALL.get()
            args = self.args if n is None else {"call": n, **self.args}
            self._ann = cls(self.name, **args)
            self._ann.__enter__()
            self._tok = _OPEN.set(self._ann)
        self._t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        dt = _now() - self._t0
        if self.field is not None:
            stats = _STATS.get()
            if stats is not None:
                setattr(stats, self.field, getattr(stats, self.field) + dt * 1e6)
        if self._ann is not None:
            _OPEN.reset(self._tok)
            self._ann.__exit__(*exc)


class scope:
    """Make ``stats`` the ``WalkStats`` that spans and counters add to."""

    __slots__ = ("stats", "_tok")

    def __init__(self, stats) -> None:
        self.stats = stats

    def __enter__(self):
        self._tok = _STATS.set(self.stats)
        return self.stats

    def __exit__(self, *exc) -> None:
        _STATS.reset(self._tok)


class call:
    """An entry point of the scheduler: a span ``name`` that numbers the
    call (a nested entry keeps its caller's number) and makes ``stats``
    current for it."""

    __slots__ = ("_span", "_scope", "_tok")

    def __init__(self, name: str, stats=None, **args) -> None:
        self._span = span(name, **args)
        self._scope = scope(stats)

    def __enter__(self) -> call:
        self._tok = _CALL.set(next(_CALLS)) if _CALL.get() is None else None
        self._scope.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        self._scope.__exit__(*exc)
        if self._tok is not None:
            _CALL.reset(self._tok)


def count(field: str, n: int) -> None:
    """Add ``n`` to counter ``field`` of the current ``WalkStats``, if any."""
    stats = _STATS.get()
    if stats is not None:
        setattr(stats, field, getattr(stats, field) + n)


@contextlib.contextmanager
def launch(host_arrays=()):
    """A sweep program's device call (``sched.launch``): one launch, and
    the ``nbytes`` of the host arrays it copies to the device."""
    nbytes = sum(a.nbytes for a in host_arrays)
    with span("sched.launch", "launch_us", h2d_bytes=nbytes):
        yield
    count("launches", 1)
    count("h2d_bytes", nbytes)


def note(**args) -> None:
    """Add ``args`` to the innermost open span's annotation, while a
    trace is recorded: what the span's opener could not know yet."""
    ann = _OPEN.get()
    if ann is not None:
        ann.set_metadata(**args)
