"""Faults planted in the program's timed path, which ``correct`` must catch.

Each is a context manager that breaks the serving engine underneath an
otherwise unchanged run; ``bench/control.py`` reads them on the chip and
``bench/tests/test_correct.py`` on the CPU.  A one-chip cell has no
exchange between chips to leave out.
"""

from __future__ import annotations

import contextlib
from unittest import mock

import jax.numpy as jnp


@contextlib.contextmanager
def carry_unchanged():
    """Every step starts from the zero state: the carry is never written."""
    from repro.serve import stream

    gather = stream.StreamingEngine._gather_states

    def forgetful(self, sessions, dtype, n_pad=0):
        kept = [s.state for s in sessions]
        for s in sessions:
            s.state = None
        try:
            return gather(self, sessions, dtype, n_pad)
        finally:
            for s, st in zip(sessions, kept):
                s.state = st

    with mock.patch.object(stream.StreamingEngine, "_gather_states",
                           forgetful):
        yield


@contextlib.contextmanager
def half_chains():
    """Each session's summary is taken over the first half of its chains."""
    from repro.serve import stream

    clf = stream.classification_summary
    with mock.patch.object(stream, "classification_summary",
                           lambda logits: clf(logits[: logits.shape[0] // 2])):
        yield


@contextlib.contextmanager
def answer_altered(call: int = 3):
    """One answer, the first of the ``call``-th summary, has its class
    probabilities rotated by one class."""
    from repro.serve import stream

    clf, calls = stream.classification_summary, [0]

    def altered(logits):
        out = clf(logits)
        calls[0] += 1
        if calls[0] != call:
            return out
        probs = out.probs.at[0].set(jnp.roll(out.probs[0], 1))
        return out._replace(probs=probs)

    with mock.patch.object(stream, "classification_summary", altered):
        yield


FAULTS = {"carry_unchanged": carry_unchanged, "half_chains": half_chains,
          "answer_altered": answer_altered}
