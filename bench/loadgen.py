"""What a traffic generator gives the harness: a schedule of chunks.

A traffic file names its generator under ``"generator"`` (``periodic``
where it names none), a module ``bench/generators/<name>.py`` that the
harness finds by that name and that defines:

* ``schedule(traffic, rng, horizon)``: a :class:`Schedule` of the chunks
  the sessions send, drawn from ``rng`` alone, with every absolute due
  time in ``[0, horizon)``;
* ``offered_rate(traffic)``: the mean chunks per second the sessions
  offer together;
* ``period_s(traffic)``: the mean time between one session's chunks, the
  deadline under which the knee sweep (``bench/sweep.py``) holds a p95.

A chunk is a row of the configuration's bank (``chunk_bank`` of its
``bench/models/<model>.py``), or its first ``lengths[i][j]`` steps where
that is not None.  A generator sees only the bank's size, from
its traffic file: nothing of the program's answers feeds back, so the
reference replays exactly the chunks that were served.

A session's chunks go out in order, one in flight at a time.  Chunk ``j``
of session ``i`` falls due at the later of ``due[i][j]`` seconds after the
traffic's start and ``after[i][j]`` seconds after the session's chunk
``j - 1`` came back to the host: ``due`` ``-inf`` with ``after`` ``think``
is a step chained to the previous result, ``after`` 0 a turn that waits
for the one before, ``after`` ``-inf`` a chunk at its fixed time.  A
session's first chunk has a finite ``due``.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Schedule:
    """Per session, one entry per chunk.  A generator may leave out
    ``lengths`` (every chunk a whole row, ``None``) and ``after`` (no chunk
    chained, ``-inf``); they are filled in here."""

    due: list[np.ndarray]       # seconds from the start
    chunks: list[np.ndarray]    # bank row
    lengths: list | None = None     # steps, None for the whole row
    after: list | None = None       # seconds after the chunk before came back

    def __post_init__(self):
        if self.lengths is None:
            self.lengths = [[None] * d.size for d in self.due]
        if self.after is None:
            self.after = [np.full(d.size, -np.inf) for d in self.due]
