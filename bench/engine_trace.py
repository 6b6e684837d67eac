"""The program's own tick spans and counters, as the benchmark reads them.

``StreamingEngine.step`` records each tick twice (``repro.serve.spans``):
its phases' host times in ``TickMetrics.phase_s`` (with ``gc_s``), and an
``engine.step`` span with one child span per phase, plus an ``rnn.layer<i>``
span per layer dispatch, on the profiler's host plane.

* :func:`phase_ms` averages ``TickMetrics.phase_s`` over the window's ticks;
* :func:`events` re-reads the newest ``.xplane.pb`` under
  ``harness.TRACE_DIR`` for the harness's spans and the program's (one parse
  a run, shared by every reader);
* :func:`launch_gap_ms_per_tick` and :func:`host_bound_idle_pct` reduce
  those events on the window :func:`bench.trace_reduce.reduce` reads: first
  harness span to last, device 0.

Each returns None where the program records no such field or span.
"""

from __future__ import annotations

import bisect
from pathlib import Path

from bench import harness, trace_reduce

STEP = "engine.step"
LAYER = "rnn.layer"


class _Names:
    """The span names :func:`events` keeps: the harness's and the program's."""

    def __contains__(self, name) -> bool:
        return (name in harness.SPANS or name.startswith("engine.")
                or name.startswith(LAYER))


_cache: dict = {}


def phase_ms(run, *names) -> float | None:
    """Mean over the window's ticks of the summed ``phase_s[name]``, in ms."""
    if not run.tick_metrics:
        return None
    total = 0.0
    for m in run.tick_metrics:
        phases = getattr(m, "phase_s", None) or {}
        if any(n not in phases for n in names):
            return None
        total += sum(phases[n] for n in names)
    return total / len(run.tick_metrics) * 1e3


def events(run) -> dict | None:
    """The traced run's spans and device operations; None untraced."""
    if run.trace is None:
        return None
    files = sorted(Path(harness.TRACE_DIR).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    key = (str(files[-1]), files[-1].stat().st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = trace_reduce.extract(files[-1], _Names())
    return _cache[key]


def _window(ev):
    """``(w0, w1, engine.step spans, device 0 ops)`` or None."""
    marks = [s for s in ev["spans"] if s[0] in harness.SPANS]
    if not marks or not ev["devices"]:
        return None
    w0 = min(s for _, s, _ in marks)
    w1 = max(e for _, _, e in marks)
    steps = sorted((s, e) for n, s, e in ev["spans"]
                   if n == STEP and s < w1 and e > w0)
    if not steps:
        return None
    ops = [(s, e, k) for _, s, e, k in ev["devices"][sorted(ev["devices"])[0]]
           if e > w0 and s < w1]
    return w0, w1, steps, ops


class _Busy:
    """Union of the device's operation intervals; time covered in a span."""

    def __init__(self, ops):
        self.merged = trace_reduce._union([(s, e) for s, e, _ in ops if e > s])
        self.starts = [s for s, _ in self.merged]

    def __call__(self, a, b) -> float:
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        t = 0
        for s, e in self.merged[i:]:
            if s >= b:
                break
            t += max(0, min(e, b) - max(s, a))
        return t


def launch_gap_ms_per_tick(ev) -> float | None:
    """Device idle time between a tick's first kernel and its last, per tick.

    A tick's kernels are those that start from its first ``rnn.layer<i>``
    span on (the host dispatching its first layer) and before the next
    tick's ``engine.step``; the idle time between the first's start and the
    last's end is the gap its layer dispatches left on the device.
    """
    if ev is None or (win := _window(ev)) is None:
        return None
    w0, w1, steps, ops = win
    layers = sorted(s for n, s, _ in ev["spans"] if n.startswith(LAYER))
    kernels = sorted((s, e) for s, e, k in ops if k)
    k_starts = [s for s, _ in kernels]
    busy = _Busy(ops)
    gaps = []
    for i, (a, b) in enumerate(steps):
        j = bisect.bisect_left(layers, a)
        if j == len(layers) or layers[j] >= b:
            continue                    # a tick that launched nothing
        end = steps[i + 1][0] if i + 1 < len(steps) else w1
        mine = kernels[bisect.bisect_left(k_starts, layers[j]):
                       bisect.bisect_left(k_starts, end)]
        if not mine:
            continue
        k0, k1 = mine[0][0], max(e for _, e in mine)
        gaps.append((k1 - k0) - busy(k0, k1))
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6


def host_bound_idle_pct(ev) -> float | None:
    """Device idle time inside ``engine.step`` spans, over the window.

    The part of ``device_idle_pct`` the host's tick path leaves, as against
    the wait for beats (``arrive_wait``) and the fetch (``block``).
    """
    if ev is None or (win := _window(ev)) is None:
        return None
    w0, w1, steps, ops = win
    busy = _Busy(ops)
    idle = 0.0
    for a, b in steps:
        a, b = max(a, w0), min(b, w1)
        idle += (b - a) - busy(a, b)
    return idle / (w1 - w0) * 100.0
