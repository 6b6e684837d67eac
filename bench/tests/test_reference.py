"""The plain references agree with the program's own reference backend.

The references import nothing of the program; this test is where the two
meet, on the CPU at a small size, over several chunks with the carry.
"""

import json

import jax
import numpy as np
import pytest

from bench import harness
from repro.serve import StreamingEngine


@pytest.mark.parametrize("config", ["ecg_clf_lstm_h8"])
def test_reference_matches_program(config):
    cell = harness.load_cell("clf_icu_pod16")
    cfg = json.loads((harness.BENCH / "configs" / f"{config}.json")
                     .read_text())
    cfg = dict(cfg, n_samples=6)
    w_seed, mc_seed, _ = harness.derive_seeds(2**31 + 5)
    traffic = dict(cell.traffic, sessions=3, max_sessions=3)
    eng = harness.build_engine(cfg, traffic, w_seed, mc_seed, 1)
    eng = StreamingEngine(eng.params, eng.cfg, backend="reference",
                          max_sessions=3, chunk_capacity=140)
    rng = np.random.default_rng(0)
    chunks = [rng.normal(size=(n, 140)).astype(np.float32) for n in (3, 2, 3)]
    sids = [f"s{i}" for i in range(3)]
    for sid in sids:
        eng.admit(sid)
    got = [[] for _ in sids]
    for j in range(3):
        out = eng.step({sids[i]: chunks[i][j] for i in range(3)
                        if len(chunks[i]) > j})
        for i, sid in enumerate(sids):
            if sid in out:
                got[i].append(jax.device_get(out[sid].summary)._asdict())
    ref_mod = harness.reference(cfg)
    ref = ref_mod.replay(cfg, w_seed, mc_seed, chunks)
    reset = ref_mod.replay(cfg, w_seed, mc_seed, chunks, carry=False)
    gaps = ref_mod.gaps(got, ref, reset)
    assert gaps["probs_gap"] < 2e-6 and gaps["wrong_answers"] == 0, gaps
    assert gaps["carry_gap_ratio"] < 0.1, gaps
    ctl = ref_mod.replay(cfg, w_seed, mc_seed, chunks, "high")
    assert ref_mod.gaps(ctl, ref, reset)["probs_gap"] > 1e-7
