"""Chat or agent sessions: bursty open-loop turns, a prompt and its decode.

A traffic file fixes the parameters:

* ``sessions``: concurrent sessions, all admitted before the window;
* ``turn_rate_per_s``: each session starts turns as a Poisson process at
  this rate, times ``burst_factor`` inside on-windows of ``burst_on_s``
  that open every ``burst_period_s`` from the traffic's start, the same
  windows for every session (so bursts hit the server together);
* ``prompt_tokens``: ``[lo, hi]``; a turn opens with one prompt chunk of
  ``floor(exp(u))`` steps, ``u`` uniform in ``[log lo, log(hi + 1))``
  (log-uniform over ``lo..hi``), at most ``chunk_capacity``;
* ``decode_steps``: ``[lo, hi]``; the prompt is followed by a number of
  decode chunks uniform over ``lo..hi``, each ``decode_chunk`` steps,
  each due ``think_s`` after the session's previous chunk came back;
* ``bank_size``: how many bank rows the chunks are cut from.

A turn that falls due while the session's previous turn is still decoding
starts when that turn's last chunk comes back (``after`` 0), so a session
never has two chunks in flight.  The schedule is drawn from the seed
alone; nothing of the program's answers feeds back.

What follows a public source, and what does not.  The structure does: a
turn is a prompt, then decode steps of which each waits for the step
before (autoregressive decoding); turns of many sessions arrive in bursts
that a Poisson process of one rate understates (BurstGPT, arXiv:2401.17644,
a trace of a deployed LLM service).  The distributions do not: the
square-wave burst shared by every session, the log-uniform prompt length,
the uniform number of decode steps and the constant ``think_s`` are
placeholders, fitted to no trace, and the file sets no default for any
of their parameters.  A cell that uses this generator fits them in its
traffic file to a trace of its kind (BurstGPT; the Azure LLM inference
trace of conversation and code requests, arXiv:2311.18677), or brings a
generator of its own where these shapes cannot fit it.
"""

from __future__ import annotations

import numpy as np

from bench.loadgen import Schedule


def _turn_rate(traffic: dict) -> float:
    """Mean turns per second of one session, bursts included."""
    on = min(float(traffic["burst_on_s"]) / float(traffic["burst_period_s"]),
             1.0)
    return float(traffic["turn_rate_per_s"]) * (
        1.0 + (float(traffic["burst_factor"]) - 1.0) * on)


def offered_rate(traffic: dict) -> float:
    """Mean chunks per second, were every chunk answered at once."""
    lo, hi = traffic["decode_steps"]
    return int(traffic["sessions"]) * _turn_rate(traffic) * (
        1.0 + (lo + hi) / 2)


def period_s(traffic: dict) -> float:
    """The mean time between one session's chunks, answered at once."""
    return int(traffic["sessions"]) / offered_rate(traffic)


def _turn_starts(traffic: dict, rng: np.random.Generator,
                 horizon: float) -> np.ndarray:
    """One session's turn starts in ``[0, horizon)``, by thinning."""
    rate, factor = float(traffic["turn_rate_per_s"]), float(
        traffic["burst_factor"])
    top = rate * max(factor, 1.0)
    t = np.sort(rng.uniform(0.0, horizon, rng.poisson(top * horizon)))
    on = (t % float(traffic["burst_period_s"])) < float(traffic["burst_on_s"])
    keep = rng.uniform(0.0, 1.0, t.size) * top < rate * np.where(on, factor,
                                                                  1.0)
    return t[keep]


def schedule(traffic: dict, rng: np.random.Generator,
             horizon: float) -> Schedule:
    """Every session's turns started in ``[0, horizon)``, with their decode."""
    cap = int(traffic["chunk_capacity"])
    p_lo, p_hi = (int(v) for v in traffic["prompt_tokens"])
    d_lo, d_hi = (int(v) for v in traffic["decode_steps"])
    step, think = int(traffic["decode_chunk"]), float(traffic["think_s"])
    if not 1 <= p_lo <= p_hi or not 1 <= step <= cap or d_lo < 0:
        raise ValueError(f"turns: bad prompt, decode or capacity in {traffic}")
    out = Schedule([], [], [], [])
    for _ in range(int(traffic["sessions"])):
        starts = _turn_starts(traffic, rng, horizon)
        prompt = np.floor(np.exp(rng.uniform(np.log(p_lo), np.log(p_hi + 1),
                                             starts.size))).astype(np.int64)
        decode = rng.integers(d_lo, d_hi + 1, starts.size)
        first = np.cumsum(decode + 1) - (decode + 1)   # each prompt's entry
        n = int(starts.size + decode.sum())
        due, after = np.full(n, -np.inf), np.full(n, think)
        lengths = np.full(n, step)
        due[first], after[first] = starts, 0.0
        lengths[first] = np.minimum(np.minimum(prompt, p_hi), cap)
        out.due.append(due)
        out.after.append(after)
        out.lengths.append(lengths)
        out.chunks.append(rng.integers(0, int(traffic["bank_size"]), n))
    return out
