"""Synthetic ECG5000-like beats: the signal the benchmark's traffic sends.

A copy of the generator in ``repro.data.ecg`` (kept here so the yardstick
does not move with the program): 140-sample beats, four classes (normal,
inverted T with ST elevation, premature R, fibrillation-like noise) in
ECG5000's imbalance, each beat normalised to zero mean and unit variance.
"""

from __future__ import annotations

import numpy as np

T_STEPS = 140
CLASS_FRACTIONS = (0.58, 0.25, 0.12, 0.05)


def _pqrst(rng: np.random.Generator, n: int) -> np.ndarray:
    t = np.linspace(0.0, 1.0, T_STEPS)[None, :]

    def bump(center, width, amp):
        c = center + rng.normal(0, 0.008, (n, 1))
        w = width * (1 + rng.normal(0, 0.08, (n, 1)))
        a = amp * (1 + rng.normal(0, 0.10, (n, 1)))
        return a * np.exp(-0.5 * ((t - c) / w) ** 2)

    x = (bump(0.18, 0.025, 0.18) + bump(0.385, 0.012, -0.25)
         + bump(0.42, 0.016, 1.60) + bump(0.455, 0.012, -0.35)
         + bump(0.68, 0.045, 0.40))
    return x + rng.normal(0, 0.015, x.shape)


def _make_class(rng: np.random.Generator, n: int, label: int) -> np.ndarray:
    x = _pqrst(rng, n)
    t = np.linspace(0.0, 1.0, T_STEPS)[None, :]
    if label == 1:
        x -= 2 * 0.40 * np.exp(-0.5 * ((t - 0.68) / 0.045) ** 2)
        x += 0.22 * ((t > 0.47) & (t < 0.62))
    elif label == 2:
        x += 1.2 * np.exp(-0.5 * ((t - 0.80) / 0.03) ** 2)
        x -= 0.8 * np.exp(-0.5 * ((t - 0.42) / 0.016) ** 2)
    elif label == 3:
        phase = rng.uniform(0, 2 * np.pi, (n, 1))
        freq = rng.uniform(9, 14, (n, 1))
        x = (0.35 * np.sin(2 * np.pi * freq * t + phase)
             + rng.normal(0, 0.12, x.shape))
    return x


def beat_bank(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` normalised beats in shuffled class order, ``[n, 140]`` float32."""
    xs = [_make_class(rng, int(round(n * f)) + 1, label)
          for label, f in enumerate(CLASS_FRACTIONS)]
    x = np.concatenate(xs)[:n]
    x = (x - x.mean(axis=1, keepdims=True)) / (x.std(axis=1, keepdims=True)
                                               + 1e-8)
    return x[rng.permutation(n)].astype(np.float32)
