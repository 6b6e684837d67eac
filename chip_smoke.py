"""Chip smoke test: serve the paper's ECG classifier end to end on a TPU.

Drives the streaming server through the launcher's own entry points
(``repro.launch.stream``) at the paper's widths — Bayesian LSTM, H=8, three
layers, MC-dropout placement YNY, p=0.125, S=30 chains per session — with
random weights from ``--seed``.  64 patient streams are live and 16 more wait
in the admission queue; each is three synthetic ECG beats served in 140-step
chunks until every stream is closed.

One chip (the default) runs four phases and fails if any does:

* serve     — ``StreamingEngine(backend="pallas_seq")``, fixed capacity 140,
              prewarmed; every stream served and closed, outputs finite;
* kernel    — the compiled serving launch holds ``tpu_custom_call``: the
              Pallas kernels ran natively, not interpreted, not the jnp scan;
* reference — the same traffic through ``backend="reference"`` on the chip:
              identical per-chunk argmax, probs and MI within :data:`TOL`;
* snapshot  — snapshot mid-run, restore into a fresh engine, finish: every
              chunk result bit-identical to the uninterrupted run.

``--chips 4`` runs only the sharded data plane (``--shards 4``, a data mesh
over four chips) against the same traffic on one chip, bit for bit, and
checks that the sharded launch's outputs sit on four distinct devices.

Usage (from the root of a checkout, on a machine with a TPU):

    python chip_smoke.py
    python chip_smoke.py --chips 4

Timings are host wall-clock after ``block_until_ready``; they are bring-up
observations, not benchmark numbers.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before serving anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

#: The paper's classifier and the traffic, as launcher flags.
PAPER_FLAGS = ["--cell", "lstm", "--hidden", "8", "--layers", "3",
               "--placement", "YNY", "--p", "0.125", "--samples", "30",
               "--sessions", "64", "--overload", "80", "--beats", "3",
               "--chunk-len", "140", "--capacity", "fixed", "--prewarm"]

#: Largest |Δ| allowed between the Pallas and reference backends on the
#: chip, for the mean probabilities and for MI.  Off the chip the two are
#: bit-identical; on it the kernels' Mosaic matmuls and the jnp scan's XLA
#: dots round differently.  A v5e measured at most 3.8e-4 (probs) and
#: 1.0e-4 (MI) over the 240 chunk results of seed 0; the bound leaves about
#: five times that for other seeds.  Argmax must match exactly regardless.
TOL = 2e-3


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _serve(flags, *, stop_at_tick=None):
    """Build, admit and serve through the launcher; time each part."""
    import jax

    from repro.launch import stream

    args = stream.parse_args(flags)
    t0 = time.perf_counter()
    eng = stream.build_engine(args, log=None)
    t1 = time.perf_counter()
    streams, labels, done = stream.open_streams(eng, args, log=None)
    queued = len(eng.queued_sessions)
    t2 = time.perf_counter()
    eng, done, served = stream.serve(eng, streams, labels, args, done=done,
                                     until_tick=stop_at_tick, log=None)
    jax.block_until_ready([r.summary for rs in served.values() for r in rs])
    t3 = time.perf_counter()
    return dict(args=args, eng=eng, streams=streams, done=done,
                served=served, queued=queued, build_s=t1 - t0,
                serve_s=t3 - t2)


def _host(served):
    """sid -> per-chunk summaries as host numpy (field tuples)."""
    import numpy as np
    return {sid: [tuple(np.asarray(v) for v in r.summary) for r in rs]
            for sid, rs in served.items()}


def _identical(a, b) -> bool:
    import numpy as np
    return a.keys() == b.keys() and all(
        len(a[s]) == len(b[s]) and all(
            all(np.array_equal(x, y) for x, y in zip(ca, cb))
            for ca, cb in zip(a[s], b[s]))
        for s in a)


def phase_serve(flags):
    import numpy as np

    run = _serve(flags + ["--backend", "pallas_seq"])
    eng, args = run["eng"], run["args"]
    total = args.overload
    _check(run["queued"] == total - args.sessions,
           f"{run['queued']} streams queued at admission, expected "
           f"{total - args.sessions}")
    _check(len(run["done"]) == total and not eng.active_sessions,
           f"{len(run['done'])}/{total} streams closed")
    chunks = -(-args.beats * 140 // args.chunk_len)
    host = _host(run["served"])
    _check(all(len(v) == chunks for v in host.values()) and len(host) == total,
           f"expected {chunks} chunks for each of {total} streams")
    _check(all(np.isfinite(x).all() for v in host.values() for c in v
               for x in c), "non-finite summary values")
    m = eng.metrics
    print(f"serve: ok streams={total} ({args.sessions} live + "
          f"{run['queued']} queued) chunks/stream={chunks} ticks={len(m)} "
          f"build+prewarm_s={run['build_s']:.3f} serve_s={run['serve_s']:.3f} "
          f"compiles_while_serving={sum(t.compiles for t in m)} "
          f"tick_s={[round(t.duration_s, 4) for t in m]}", flush=True)
    return run, host


def phase_kernel(run):
    import jax

    from repro.serve.scheduler import launch_args

    eng = run["eng"]
    t0 = time.perf_counter()
    text = jax.jit(eng._apply).lower(
        *launch_args(eng, eng.chunk_capacity)).compile().as_text()
    n = text.count("tpu_custom_call")
    _check(n > 0, "no tpu_custom_call in the compiled serving launch")
    print(f"kernel: ok tpu_custom_call x{n} in the compiled launch "
          f"(T={eng.chunk_capacity}, rows={launch_args(eng, 1)[1].shape[0]}) "
          f"compile_s={time.perf_counter() - t0:.3f}", flush=True)


def phase_reference(flags, host):
    import numpy as np

    run = _serve(flags + ["--backend", "reference"])
    ref = _host(run["served"])
    _check(ref.keys() == host.keys(), "reference served other streams")
    argmax_diff, d_probs, d_mi = 0, 0.0, 0.0
    for sid, chunks in host.items():
        for a, b in zip(chunks, ref[sid]):
            argmax_diff += int(np.argmax(a[0]) != np.argmax(b[0]))
            d_probs = max(d_probs, float(np.abs(a[0] - b[0]).max()))
            d_mi = max(d_mi, float(np.abs(a[3] - b[3]).max()))
    n = sum(len(v) for v in host.values())
    print(f"reference: argmax_mismatch={argmax_diff}/{n} "
          f"max|d_probs|={d_probs!r} max|d_MI|={d_mi!r} tol={TOL!r} "
          f"serve_s={run['serve_s']:.3f}", flush=True)
    _check(argmax_diff == 0, f"{argmax_diff} chunks classify differently")
    _check(d_probs <= TOL and d_mi <= TOL, "reference |d| above tolerance")


def phase_snapshot(flags, host):
    with tempfile.TemporaryDirectory() as snap:
        snap_flags = flags + ["--backend", "pallas_seq", "--snapshot-dir",
                              snap, "--snapshot-every", "2"]
        first = _serve(snap_flags, stop_at_tick=2)
        _check(first["eng"].tick == 2 and first["eng"].active_sessions,
               "the first engine did not stop mid-run")
        t0 = time.perf_counter()
        rest = _serve(snap_flags + ["--resume"])
        resumed_s = time.perf_counter() - t0
    got = _host(first["served"])
    for sid, chunks in _host(rest["served"]).items():
        got.setdefault(sid, []).extend(chunks)
    same = _identical(got, host)
    print(f"snapshot: bit_identical={same} snapshot_tick=2 "
          f"restore+finish_s={resumed_s:.3f}", flush=True)
    _check(same, "restored run differs from the uninterrupted run")


def phase_sharded(flags):
    import jax

    from repro.serve.scheduler import launch_args

    one = _serve(flags + ["--backend", "pallas_seq"])
    four = _serve(flags + ["--backend", "pallas_seq", "--shards", "4"])
    same = _identical(_host(four["served"]), _host(one["served"]))
    eng = four["eng"]
    outs, states = eng._apply(*launch_args(eng, eng.chunk_capacity))
    devs = {s.device for s in states[0][0].addressable_shards}
    logit_devs = {s.device for s in outs[0].addressable_shards}
    print(f"sharded: bit_identical={same} carry_devices={len(devs)} "
          f"logit_devices={len(logit_devs)} serve_s_4chips="
          f"{four['serve_s']:.3f} serve_s_1chip={one['serve_s']:.3f}",
          flush=True)
    _check(same, "4-chip results differ from 1-chip results")
    _check(len(devs) == 4, f"carry shards on {len(devs)} devices, not 4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: only the sharded "
                    "data plane against one chip")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch import compile_cache

    print(f"device: {devs[0].device_kind} x{len(devs)} | compile cache "
          f"{compile_cache.enable()}", flush=True)
    flags = PAPER_FLAGS + ["--seed", str(opts.seed)]
    if opts.chips == 4:
        phase_sharded(flags)
    else:
        run, host = phase_serve(flags)
        phase_kernel(run)
        phase_reference(flags, host)
        phase_snapshot(flags, host)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
