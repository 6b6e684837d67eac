"""Open-loop periodic sessions: an ECG monitor's beats, one chunk a beat.

Every session is a patient's monitor sending one bank row (a 140-sample
beat) per heartbeat.  A traffic file fixes the parameters:

* ``sessions``: concurrent sessions, all admitted before the window;
* ``hr_bpm``: ``[lo, hi]`` heart rates.  The rates are the same evenly
  spaced set for every seed (``lo + (hi - lo) * (i + 0.5) / sessions``), so
  the offered load does not move with the seed; the seed only deals them
  out to sessions;
* ``beat_jitter``: each beat's period is ``60 / hr`` times ``1 + u``, ``u``
  uniform in ``[-beat_jitter, beat_jitter]``;
* ``beat_bank``: how many distinct beats the seed draws to choose from.

A session's first beat falls due at a uniform phase within its first
period, so arrivals are spread from the start.  No chunk is chained.
"""

from __future__ import annotations

import numpy as np

from bench.loadgen import Schedule


def heart_rates(traffic: dict) -> np.ndarray:
    n = int(traffic["sessions"])
    lo, hi = traffic["hr_bpm"]
    return lo + (hi - lo) * (np.arange(n) + 0.5) / n


def offered_rate(traffic: dict) -> float:
    """Mean chunks per second the sessions offer together."""
    return float(np.sum(heart_rates(traffic) / 60.0))


def period_s(traffic: dict) -> float:
    """The mean beat period."""
    lo, hi = traffic["hr_bpm"]
    return 60.0 / ((lo + hi) / 2)


def schedule(traffic: dict, rng: np.random.Generator,
             horizon: float) -> Schedule:
    """Due times in ``[0, horizon)`` and the beat each chunk carries."""
    hr = heart_rates(traffic)[rng.permutation(int(traffic["sessions"]))]
    jitter = float(traffic["beat_jitter"])
    due, beats = [], []
    for rate in hr:
        period = 60.0 / rate
        n_max = int(horizon / (period * (1 - jitter))) + 2
        steps = period * (1 + rng.uniform(-jitter, jitter, n_max))
        t = rng.uniform(0, period) + np.concatenate([[0.0],
                                                     np.cumsum(steps)])
        t = t[t < horizon]
        due.append(t)
        beats.append(rng.integers(0, int(traffic["beat_bank"]), t.size))
    return Schedule(due, beats)
