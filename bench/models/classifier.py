"""The program's MC-dropout classifier as a cell builds, feeds and counts it.

A model family is one file here, found by the ``model`` of a configuration
file: ``build`` makes the program's config and names its ``init``,
``chunk_bank`` draws the rows the traffic's chunks are cut from,
``layer_widths`` gives the recurrent kernel's launches of one tick and
``model_flops`` the matmul work of the chain-steps served.  A family may
also define ``engine_options(cfg, traffic)``, further keywords of
``repro.serve.StreamingEngine``; this one needs none.
"""

from bench import ecg, roofline


def build(cfg: dict, mc):
    """``(init, program config)`` for ``repro.core.classifier``."""
    from repro.core import classifier

    return classifier.init, classifier.ClassifierConfig(
        input_dim=cfg["input_dim"], hidden=cfg["hidden"],
        num_layers=cfg["num_layers"], num_classes=cfg["num_classes"],
        cell=cfg["cell"], mcd=mc)


def chunk_bank(rng, cfg: dict, traffic: dict):
    """``beat_bank`` synthetic ECG beats ``[n, 140]``, float32."""
    return ecg.beat_bank(rng, int(traffic["beat_bank"]))


def layer_widths(cfg: dict) -> list[tuple[int, int]]:
    """``(I, H)`` of every kernel launch of one tick, in launch order."""
    dims = [cfg["input_dim"]] + [cfg["hidden"]] * cfg["num_layers"]
    return list(zip(dims[:-1], dims[1:]))


def model_flops(cfg: dict, chain_steps: int, chunks_chains: int) -> int:
    """Matmul FLOPs for ``chain_steps`` served chain-steps; the dense head
    runs once per (chunk, chain) pair, ``chunks_chains`` of them."""
    g = roofline.gates(cfg)
    per_step = sum(2 * g * (i + h) * h for i, h in layer_widths(cfg))
    return (chain_steps * per_step
            + chunks_chains * 2 * cfg["hidden"] * cfg["num_classes"])
