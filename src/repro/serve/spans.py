"""Spans and counters of one engine tick, on the host clock and the profiler's.

:func:`tick` opens the record of one ``StreamingEngine.step`` and
:meth:`Tick.phase` times one block of it.  Each does two things at once:

* it adds the block's elapsed ``time.perf_counter`` to the open record
  (``Tick.phase_s[name]``, ``Tick.duration_s``), which ``TickMetrics``
  carries to the metrics sink and the JSONL trail;
* it enters ``jax.profiler.TraceAnnotation(name, tick=<n>)``.  While a
  profiler session runs, that span lands on the profiler's host plane, on
  the device trace's clock, so an idle gap on the device can be put down to
  the phase the host was in; with no session it costs about a microsecond.

The record also counts what happened anywhere in the process while the tick
ran: backend compiles (``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration``, eager ops included) and
Python GC pauses (``gc.callbacks``; each collection is also an
``engine.gc`` span).  Both hooks are installed once, on the first tick.
One thread steps an engine at a time; a compile or a collection on another
thread still counts in the open tick.
"""

from __future__ import annotations

import contextlib
import gc
import time

import jax

#: The child spans of ``engine.step``, in the order ``step`` runs them; the
#: keys of ``TickMetrics.phase_s``.
PHASES = ("engine.drain", "engine.stage", "engine.carry_gather",
          "engine.launch", "engine.summarize", "engine.writeback",
          "engine.early_exit", "engine.escalate")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Tick:
    """Host time of one tick and of its phases; compiles and GC inside it."""

    def __init__(self, number: int):
        self.number = number
        self.phase_s = dict.fromkeys(PHASES, 0.0)
        self.duration_s = 0.0
        self.gc_s = 0.0
        self.compiles = 0

    @contextlib.contextmanager
    def phase(self, name: str):
        """Span ``name``; its host time adds to ``phase_s[name]``."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, tick=self.number):
                yield
        finally:
            self.phase_s[name] += time.perf_counter() - t0


class _Totals:
    """Process-wide counts the hooks keep; a tick reads their deltas."""

    installed = False
    compiles = 0
    gc_s = 0.0
    gc_open = None          # (start, span) of the collection running now


def _on_compile(event, duration, **_):
    if event == COMPILE_EVENT:
        _Totals.compiles += 1


def _on_gc(stage, info):
    if stage == "start":
        span = jax.profiler.TraceAnnotation("engine.gc")
        span.__enter__()
        _Totals.gc_open = (time.perf_counter(), span)
    elif _Totals.gc_open is not None:
        t0, span = _Totals.gc_open
        _Totals.gc_open = None
        _Totals.gc_s += time.perf_counter() - t0
        span.__exit__(None, None, None)


def _install():
    if not _Totals.installed:
        _Totals.installed = True
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        gc.callbacks.append(_on_gc)


@contextlib.contextmanager
def tick(number: int):
    """The ``engine.step`` span of tick ``number``; yields its :class:`Tick`,
    complete once the block has left."""
    _install()
    rec = Tick(number)
    gc0, compiles0 = _Totals.gc_s, _Totals.compiles
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("engine.step", tick=number):
            yield rec
    finally:
        rec.duration_s = time.perf_counter() - t0
        rec.gc_s = _Totals.gc_s - gc0
        rec.compiles = _Totals.compiles - compiles0
