"""The traffic generators: same load for every seed, same inputs per seed."""

import numpy as np
import pytest

from bench import ecg, harness

TRAFFIC = {"sessions": 16, "hr_bpm": [60, 100], "beat_jitter": 0.05,
           "beat_bank": 64}
TURNS = {"generator": "turns", "sessions": 8, "chunk_capacity": 140,
         "turn_rate_per_s": 0.5, "burst_factor": 4.0, "burst_on_s": 5.0,
         "burst_period_s": 20.0, "prompt_tokens": [16, 512],
         "decode_steps": [4, 12], "decode_chunk": 1, "think_s": 0.1,
         "bank_size": 64}
periodic = harness.generator(TRAFFIC)
turns = harness.generator(TURNS)


def _equal(a, b):
    for name in ("due", "chunks", "lengths", "after"):
        x, y = getattr(a, name), getattr(b, name)
        assert len(x) == len(y), name
        for u, v in zip(x, y):
            np.testing.assert_array_equal(u, v)


def test_same_seed_same_schedule():
    _equal(periodic.schedule(TRAFFIC, np.random.default_rng(5), 30.0),
           periodic.schedule(TRAFFIC, np.random.default_rng(5), 30.0))


def test_every_seed_offers_the_same_rates():
    rates = []
    for seed in (1, 2, 2**31 + 9):
        s = periodic.schedule(TRAFFIC, np.random.default_rng(seed), 600.0)
        per = sorted(d.size / 600.0 for d in s.due)
        rates.append(per)
    np.testing.assert_allclose(rates[0], rates[1], rtol=0.02)
    np.testing.assert_allclose(rates[0], rates[2], rtol=0.02)
    assert periodic.offered_rate(TRAFFIC) == sum(
        periodic.heart_rates(TRAFFIC)) / 60
    assert periodic.period_s(TRAFFIC) == 60.0 / 80


def test_periods_within_jitter_and_first_beat_within_a_period():
    s = periodic.schedule(TRAFFIC, np.random.default_rng(3), 60.0)
    hr = sorted(periodic.heart_rates(TRAFFIC))
    assert all(m is None for x in s.lengths for m in x)    # whole beats
    assert all(np.isneginf(a).all() for a in s.after)     # none chained
    for d in s.due:
        gaps = np.diff(d)
        period = np.median(gaps)
        assert np.all(np.abs(gaps / period - 1) <= 0.11)
        assert 0 <= d[0] <= 60 / hr[0]


def test_seeds_derive_deterministically_for_large_seeds():
    big = 2**31 + 123456
    assert harness.derive_seeds(big) == harness.derive_seeds(big)
    assert harness.derive_seeds(big) != harness.derive_seeds(big + 1)
    w, m, _ = harness.derive_seeds(big)
    assert 0 <= w < 2**31 and 0 <= m < 2**31


def test_beats_are_normalised():
    x = ecg.beat_bank(np.random.default_rng(0), 32)
    assert x.shape == (32, 140) and x.dtype == np.float32
    np.testing.assert_allclose(x.mean(1), 0, atol=1e-5)
    np.testing.assert_allclose(x.std(1), 1, atol=1e-3)


@pytest.mark.parametrize("seed", [4, 2**31 + 77])
def test_turns_same_seed_same_schedule(seed):
    a = turns.schedule(TURNS, np.random.default_rng(seed), 120.0)
    _equal(a, turns.schedule(TURNS, np.random.default_rng(seed), 120.0))
    b = turns.schedule(TURNS, np.random.default_rng(seed + 1), 120.0)
    assert any(x.size != y.size or (x != y).any()
               for x, y in zip(a.chunks, b.chunks))


def test_turns_prompts_then_chained_decode_steps():
    s = turns.schedule(TURNS, np.random.default_rng(11), 300.0)
    prompts, steps = [], []
    for due, rows, lengths, after in zip(s.due, s.chunks, s.lengths,
                                         s.after):
        first = np.flatnonzero(np.isfinite(due))
        assert first[0] == 0 and (np.diff(due[first]) >= 0).all()
        assert (due[first] >= 0).all() and (due[first] < 300.0).all()
        # A prompt waits for the turn before; a decode step is chained.
        assert (after[first] == 0).all()
        decode = np.setdiff1d(np.arange(due.size), first)
        assert (after[decode] == TURNS["think_s"]).all()
        assert (lengths[decode] == TURNS["decode_chunk"]).all()
        steps += list(np.diff(np.append(first, due.size)) - 1)
        prompts += list(lengths[first])
        assert ((rows >= 0) & (rows < TURNS["bank_size"])).all()
    assert min(steps) == 4 and max(steps) == 12
    # Log-uniform over 16..512, cut at the capacity of 140: a share of
    # 1 - log(140/16)/log(513/16) = 0.37 at the cut.
    prompts = np.array(prompts)
    assert prompts.min() >= 16 and prompts.max() == 140
    assert 0.3 < np.mean(prompts == 140) < 0.45
    assert np.median(prompts) < 100


def test_turns_offer_their_rate_with_bursts():
    s = turns.schedule(TURNS, np.random.default_rng(2), 2000.0)
    starts = np.concatenate([d[np.isfinite(d)] for d in s.due])
    per_session = starts.size / TURNS["sessions"] / 2000.0
    # 0.5/s off, 2/s inside 5 s of every 20: 0.875 turns/s.
    assert per_session == pytest.approx(0.875, rel=0.05)
    on = (starts % 20.0) < 5.0
    assert on.mean() == pytest.approx(2.0 * 5 / (0.875 * 20), rel=0.05)
    chunks = sum(d.size for d in s.due) / 2000.0
    assert chunks == pytest.approx(turns.offered_rate(TURNS), rel=0.05)
    assert turns.period_s(TURNS) == pytest.approx(
        TURNS["sessions"] / turns.offered_rate(TURNS))
