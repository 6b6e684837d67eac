"""Device idle time between a tick's first kernel and its last, per tick.

The gaps the host's layer-by-layer dispatch leaves between the kernels of
one tick, from the trace (``bench/engine_trace.py``).
"""

from bench import engine_trace


def read(run):
    return engine_trace.launch_gap_ms_per_tick(engine_trace.events(run))
