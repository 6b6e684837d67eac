"""Share of the traced window the device idles inside ``engine.step`` spans.

The part of ``device_idle_pct`` that the host's tick path causes, not the
wait for beats, from the trace (``bench/engine_trace.py``).
"""

from bench import engine_trace


def read(run):
    return engine_trace.host_bound_idle_pct(engine_trace.events(run))
