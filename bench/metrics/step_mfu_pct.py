"""Model FLOPs of the chain-steps served, over tick time times peak FLOP/s.

The whole tick's share of the chip's peak: the matmul FLOPs the model needs
for the live chain-steps of the window's ticks (``bench/models/``),
over the summed tick time (``step`` plus the fetch of its summaries) times
the peak FLOP/s of every chip the cell uses.
"""

from bench import harness, roofline


def read(run):
    if not run.ticks:
        return None
    steps = sum(m.live_chain_steps for m in run.tick_metrics)
    chains = sum(m.live_rows for m in run.tick_metrics)
    flops = harness.model(run.cell.cfg).model_flops(run.cell.cfg, steps,
                                                   chains)
    busy = sum(e - s for s, e, _ in run.ticks)
    peak = roofline.peaks(run.device_kind)[0] * run.chips
    return flops / (busy * peak) * 100.0
