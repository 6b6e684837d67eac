"""The traffic generator: same load for every seed, same inputs per seed."""

import numpy as np

from bench import ecg, harness, loadgen

TRAFFIC = {"sessions": 16, "hr_bpm": [60, 100], "beat_jitter": 0.05,
           "beat_bank": 64}


def test_same_seed_same_schedule():
    a = loadgen.schedule(TRAFFIC, np.random.default_rng(5), 30.0)
    b = loadgen.schedule(TRAFFIC, np.random.default_rng(5), 30.0)
    for x, y in zip(a.due + a.beats, b.due + b.beats):
        np.testing.assert_array_equal(x, y)


def test_every_seed_offers_the_same_rates():
    rates = []
    for seed in (1, 2, 2**31 + 9):
        s = loadgen.schedule(TRAFFIC, np.random.default_rng(seed), 600.0)
        per = sorted(d.size / 600.0 for d in s.due)
        rates.append(per)
    np.testing.assert_allclose(rates[0], rates[1], rtol=0.02)
    np.testing.assert_allclose(rates[0], rates[2], rtol=0.02)
    assert loadgen.offered_rate(TRAFFIC) == sum(
        loadgen.heart_rates(TRAFFIC)) / 60


def test_periods_within_jitter_and_first_beat_within_a_period():
    s = loadgen.schedule(TRAFFIC, np.random.default_rng(3), 60.0)
    hr = sorted(loadgen.heart_rates(TRAFFIC))
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
