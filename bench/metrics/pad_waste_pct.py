"""Share of launched chain-steps that carried no session's data.

``1 - sum(live_chain_steps) / sum(padded_steps)`` over the window's ticks,
from the engine's own per-tick counts.
"""


def read(run):
    padded = sum(m.padded_steps for m in run.tick_metrics)
    if not padded:
        return None
    live = sum(m.live_chain_steps for m in run.tick_metrics)
    return (1.0 - live / padded) * 100.0
