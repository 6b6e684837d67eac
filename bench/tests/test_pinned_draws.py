"""The ECG cell draws and reads what it did before the harness was made
generic over families and generators.

SHA-256 digests, for three seeds, taken with the harness as it stood
before (``ecg.beat_bank``, ``loadgen.schedule`` and the inline warm-up draw
called directly, and the same short run as here): of the
``clf_icu_pod16`` bank, its schedule's due times and bank rows at the
benchmark's ``run_seconds``, and the warm-up draws of every tick size;
and, from a run of the cell at two sessions on the CPU, of the warm-up
chunks, of every session's chunks as served, in order, and of the chunks
the reference replays for the check.  None of these depends on timing:
every chunk due before the window closes is served, and each session's
chunks go out in the schedule's order.  The check's numbers do (which
sessions share a tick moves the served floats by rounding), so the run
is held to its limits only.
"""

import hashlib
import types

import numpy as np
import pytest

from bench import harness

CELL = "clf_icu_pod16"
KEYS = ("bank", "due", "chunks", "warmup", "warmup_chunks",
        "served_chunks", "replayed")
PINNED = {
    7: dict(zip(KEYS, (
        "632934c58d4a6296dd996789779799ceb4c701800600564502805dabddb74a6d",
        "9c4eeaff19c2ec408146bfe573e43a4666ac80f5c1276cadf7918ba2638628ec",
        "86af1e875a574b2210231739bd00dd97cef1512670698e61beed6030f063747b",
        "39f30c3f2c210a22d15c789500ebdeda7d51a7fb4270547c3604a6efee5d2b01",
        "0ea09207594ab2826e668bd7021675496e5758aeb5752ab28aa4bd64313f068a",
        "e73aebef02b50479f6ac197d50c9d8f1801fa2de8bf35e31fe17938ea3cd4252",
        "49be1cfda42e59764e135f6db2f50449191b2b9bb67ec80b1f4098e746088245",
    ))),
    2147483659: dict(zip(KEYS, (
        "c1040fe27406168512b652c3fe6d90246aae15456efa2e75b307ad7d1c4ee8aa",
        "a21baaf04feb8895e298df313b8a5c1b6521162e474df84de0d3202fbfdb91cc",
        "58ca2231d6b5268e04e149ec730f0afb56ef12222bbd93caa12b06c91c71c2b1",
        "af268726a3fd6cc7f3fc821937df176b770267e4a01924ca9360db3a45e801ac",
        "16883648cb21e2558e593da1eec1b1e946de259bc9bfa3d3498c2e0912718d10",
        "28f8e6a95219f4e039d400577378c969defe4444f0f5788234f6c456c79b6665",
        "36edb11dfd55635be0ff58c274a26bae25fa3a72097cf94cc923048004ed2b16",
    ))),
    2147500001: dict(zip(KEYS, (
        "1577feb845a734e418d2f1d9053402fc7355039507d82e196845ae80247c9ed5",
        "3805e779fc001d777eadba7b78bfecd05fc96d9df15bbcc31eb1234a89fcb05d",
        "445daa4306074761dddd94369aa1432cf9dc05b5edd7ee215ed314b48b94955f",
        "b8bcf0a2548b66e31b92c54c508aa556b7f845f857db0bfbe412d2a38facec18",
        "9e995539d13eac827cdfe3b4cf9bdb2c0beea8b5362cbf0dbf5905b4aa1f3d39",
        "ce934f9c8ae4c9ef5407a6317ba4b10f90fffb0561e6bbf1102404221cf67c63",
        "6ab70b81a9ab60decab412c89621c336c8074c78433b8191caed6209ff2d6ea6",
    ))),
}


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_draws_match(seed):
    cell = harness.load_cell(CELL)
    cfg, traffic = cell.cfg, cell.traffic
    _, _, t_seed = harness.derive_seeds(seed)
    rng = np.random.default_rng(t_seed)
    bank = harness.model(cfg).chunk_bank(rng, cfg, traffic)
    sched = harness.generator(traffic).schedule(
        traffic, rng, float(traffic["warmup_s"]) + cell.spec["run_seconds"])
    # The harness's warm-up: one tick of every size, largest first.
    warm = [rng.integers(0, len(bank), k)
            for k in range(int(traffic["sessions"]), 0, -1)]
    got = {"bank": _digest(bank), "due": _digest(*sched.due),
           "chunks": _digest(*sched.chunks), "warmup": _digest(*warm)}
    assert got == {k: PINNED[seed][k] for k in got}
    assert all(np.isneginf(a).all() for a in sched.after)   # none chained


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_short_run_matches(seed, tmp_path, monkeypatch):
    from repro.serve import stream

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cell = harness.load_cell(CELL)
    cell.traffic = dict(cell.traffic, sessions=2, max_sessions=2,
                        warmup_s=0.3)
    calls, replays = [], []
    step, find = stream.StreamingEngine.step, harness.reference

    def spy_step(self, chunks):
        calls.append({s: np.array(c) for s, c in chunks.items()})
        return step(self, chunks)

    def spy_reference(cfg):
        ref = find(cfg)

        def replay(cfg, w_seed, mc_seed, chunks, matmul="highest",
                   carry=True):
            replays.append((carry, matmul, chunks))
            return ref.replay(cfg, w_seed, mc_seed, chunks, matmul, carry)
        return types.SimpleNamespace(replay=replay, gaps=ref.gaps)

    monkeypatch.setattr(stream.StreamingEngine, "step", spy_step)
    monkeypatch.setattr(harness, "reference", spy_reference)
    run, numbers = harness.run(CELL, seed, 2.0, False, require_chip=False,
                               cell=cell)
    per = {}
    for c in calls:
        for s, x in c.items():
            per.setdefault(s, []).append(x)
    # With and without the carry, over one array of whole beats a session.
    assert [(c, m) for c, m, _ in replays] == [(True, "highest"),
                                               (False, "highest")]
    assert all(type(x) is np.ndarray for _, _, ch in replays for x in ch)
    got = {"warmup_chunks": _digest(*[x for c in calls[:2]
                                      for _, x in sorted(c.items())]),
           "served_chunks": _digest(*[x for s in sorted(per)
                                      for x in per[s]]),
           "replayed": _digest(*replays[0][2])}
    assert _digest(*replays[1][2]) == got["replayed"]
    assert got == {k: PINNED[seed][k] for k in got}
    assert harness.is_correct(run, numbers), numbers
