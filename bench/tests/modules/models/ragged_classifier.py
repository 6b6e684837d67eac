"""A family for the tests alone: the classifier fed ECG segments of varied
length.

It reuses the classifier's build and counts, draws its bank from its own
traffic key and passes the engine a keyword of its traffic's, so that a
test can see both hooks reach the harness.
"""

from bench import ecg
from bench.models.classifier import build, layer_widths, model_flops

__all__ = ["build", "chunk_bank", "engine_options", "layer_widths",
           "model_flops"]


def chunk_bank(rng, cfg: dict, traffic: dict):
    """``bank_size`` beats; the traffic serves prefixes of varied length."""
    return ecg.beat_bank(rng, int(traffic["bank_size"]))


def engine_options(cfg: dict, traffic: dict) -> dict:
    return {"metrics_window": int(traffic["metrics_window"])}
