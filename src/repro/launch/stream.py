"""Streaming session-serving launcher: continuous ECG monitoring.

Opens concurrent sessions, each an unbounded synthetic-ECG signal
(concatenated ECG5000-compatible beats), and decodes them chunk-by-chunk
through the sequence-fused Pallas kernel with carried per-session state —
per-chunk Bayesian uncertainty over the signal-so-far.

The PR 3 control plane is wired in: ``--overload`` admits more streams
than the store holds (they wait in the priority admission queue and go
live as rows free up), ``--capacity auto`` lets the adaptive scheduler
pick the launch shape per tick, and ``--snapshot-dir``/``--resume`` make
the whole thing crash-safe — kill the process at any tick and relaunch
with ``--resume`` to continue every stream bit-identically.

The PR 5 multi-device data plane rides the same loop: ``--shards N``
partitions every launch's batch rows (sessions × MC chains) over the first
N devices (``repro.launch.rnn_shardings``) with bit-identical results,
``--prewarm`` compiles every capacity rung before the first tick, and
``--metrics-out`` streams per-tick ``TickMetrics`` to a JSONL file.

``--controller`` closes the DSE→serving loop online: a
``CoDesignController`` watches the tick metrics, calibrates the roofline
against observed latency, and under an SLO breach (``--slo-p95-ms``,
``--min-tokens-per-sec``) re-runs the paper's optimization over the live
knobs — swapping the winning config in at a tick boundary with every
session's stream continuing bit-identically.  ``--decisions-out`` appends
each ``DecisionRecord`` as a JSON line.

``--early-exit-threshold`` makes S per-session state: every stream still
*opens* with ``--samples`` chains (the engine ceiling), but once a
session's uncertainty summary has converged — dropping half its chains
would move the summary by at most the threshold — the engine retires the
surplus rows mid-stream (never below ``--min-samples``).  Confident
streams get cheaper; uncertain ones keep the full posterior sample.
``0.0`` is the strictest setting (retire only exactly-converged
summaries); the flag is incompatible with ``--shards``.

``--tenants fleet.json`` switches to multi-tenant fleet serving (ISSUE 8):
the JSON declares heterogeneous tenants — classifier or autoencoder, LSTM
or GRU, each with its own S, precision and priority weight — and one
``FleetEngine`` serves all of them per tick (same-config tenants fold into
shared launch groups; admission is weighted-fair under overload).  The
other serving flags (``--chunk-len``, ``--metrics-out``,
``--snapshot-dir``, ``--resume``) apply fleet-wide.

Usage:
  PYTHONPATH=src python -m repro.launch.stream --sessions 4 --chunk-len 20 \
      --samples 8 --beats 2 --backend pallas_seq
  PYTHONPATH=src python -m repro.launch.stream --tenants fleet.json \
      --chunk-len 20 --metrics-out /tmp/fleet.jsonl
  PYTHONPATH=src python -m repro.launch.stream --sessions 4 --cell gru
  PYTHONPATH=src python -m repro.launch.stream --sessions 2 --overload 6 \
      --capacity auto --snapshot-dir /tmp/snap --snapshot-every 3
  PYTHONPATH=src python -m repro.launch.stream --sessions 2 --overload 6 \
      --capacity auto --snapshot-dir /tmp/snap --resume
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.stream --sessions 8 --shards 8 \
      --capacity auto --prewarm --metrics-out /tmp/ticks.jsonl
  PYTHONPATH=src python -m repro.launch.stream --sessions 4 --samples 8 \
      --capacity auto --controller --slo-p95-ms 30 \
      --decisions-out /tmp/decisions.jsonl
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint
from repro.core import autoencoder as ae, classifier as clf, mcd
from repro.data import ecg
from repro.launch import compile_cache
from repro.serve import (FleetEngine, JsonlSink, StreamingEngine, TenantSpec,
                         pow2_ladder, prewarm, summarize)


def build_streams(n_sessions: int, beats: int, seed: int):
    """Per-session continuous signals: `beats` ECG beats back to back."""
    _, _, ex, ey = ecg.make_ecg5000(seed)
    rng = np.random.default_rng(seed)
    streams, labels = [], []
    for _ in range(n_sessions):
        idx = rng.integers(0, len(ex), size=beats)
        streams.append(np.concatenate([ex[i] for i in idx], axis=0))
        labels.append([int(ey[i]) for i in idx])
    return streams, labels


def load_fleet(path: str, default_seed: int):
    """Parse a fleet JSON tenant table into ``TenantSpec``s + stream plans.

    Schema (every per-tenant key optional except ``name``)::

        {"admit_per_tick": 4, "aging_rounds": 16, "max_pending": 256,
         "tenants": [
           {"name": "ward", "task": "classifier", "cell": "lstm",
            "hidden": 8, "layers": 2, "classes": 5, "samples": 4,
            "p": 0.125, "placement": "YN", "weight": 3.0,
            "precision": null, "backend": "pallas_seq",
            "max_sessions": 4, "streams": 6, "beats": 2,
            "decode_window": null, "seed": 0,
            "early_exit_threshold": null, "min_samples": 1},
           ...]}

    ``streams`` is how many signals the tenant submits (> ``max_sessions``
    overloads its row quota and exercises the weighted-fair queue);
    ``decode_window`` truncates autoencoder replay to the last W steps.
    Tenants declaring identical model spec *and* seed share one params
    object, so the fleet folds them into a shared launch group.
    """
    with open(path) as fh:
        doc = json.load(fh)
    specs, plans, params_cache = [], {}, {}
    for e in doc["tenants"]:
        name = e["name"]
        task = e.get("task", "classifier")
        layers = int(e.get("layers", 2))
        m = mcd.MCDConfig(
            p=float(e.get("p", 0.125)),
            placement=e.get("placement") or "Y" + "N" * (layers - 1),
            n_samples=int(e.get("samples", 4)),
            seed=int(e.get("seed", default_seed)))
        if task == "classifier":
            cfg = clf.ClassifierConfig(
                hidden=int(e.get("hidden", 8)), num_layers=layers,
                num_classes=int(e.get("classes", 5)),
                cell=e.get("cell", "lstm"), mcd=m)
            init = clf.init
        elif task == "autoencoder":
            cfg = ae.AutoencoderConfig(
                hidden=int(e.get("hidden", 8)), num_layers=layers,
                cell=e.get("cell", "lstm"), mcd=m,
                decode_window=e.get("decode_window"))
            init = ae.init
        else:
            raise ValueError(f"tenant {name!r}: unknown task {task!r} "
                             "(classifier | autoencoder)")
        key = (task, cfg, m.seed)
        if key not in params_cache:
            params_cache[key] = init(jax.random.key(m.seed), cfg)
        max_sessions = int(e.get("max_sessions", 4))
        eet = e.get("early_exit_threshold")
        specs.append(TenantSpec(
            name=name, cfg=cfg, params=params_cache[key],
            weight=float(e.get("weight", 1.0)),
            precision=e.get("precision"),
            backend=e.get("backend", "pallas_seq"),
            max_sessions=max_sessions,
            early_exit_threshold=None if eet is None else float(eet),
            min_samples=int(e.get("min_samples", 1))))
        plans[name] = {"streams": int(e.get("streams", max_sessions)),
                       "beats": int(e.get("beats", 2)),
                       "seed": int(e.get("seed", default_seed))}
    fleet_kw = {k: doc[k] for k in ("admit_per_tick", "aging_rounds",
                                    "max_pending") if k in doc}
    return specs, plans, fleet_kw


def run_fleet(args):
    """Serve a multi-tenant fleet declared by ``--tenants fleet.json``."""
    specs, plans, fleet_kw = load_fleet(args.tenants, args.seed)
    sink = JsonlSink(args.metrics_out) if args.metrics_out else None
    fleet = FleetEngine(specs, metrics_sink=sink, **fleet_kw)
    for g in fleet.groups.values():
        print(f"launch group {g.name}: tenants={g.tenants}")
    print(f"fleet of {len(specs)} tenant(s), "
          f"admit_per_tick={fleet.admit_per_tick or 'eager'} | "
          + " ".join(f"{s.name}[w={s.weight:g} rows={s.max_sessions} "
                     f"streams={plans[s.name]['streams']}]" for s in specs))

    # Streams regenerate deterministically from the tenant table, so a
    # resume only needs the snapshot + the same fleet.json.
    streams = {t: build_streams(p["streams"], p["beats"], p["seed"])[0]
               for t, p in plans.items()}
    planned = {t: [f"s{k}" for k in range(p["streams"])]
               for t, p in plans.items()}
    done: dict[str, set[str]] = {t: set() for t in plans}
    if args.resume:
        fleet.restore(args.snapshot_dir)
        live = fleet.active_sessions
        queued = {(t.tenant, t.sid.split("/", 1)[1])
                  for t in fleet.queue.waiting()}
        # Everything was admitted before the first snapshot, so a planned
        # sid that is neither live nor queued has already finished.
        for t in plans:
            done[t] = {s for s in planned[t]
                       if s not in live.get(t, []) and (t, s) not in queued}
        print(f"resumed fleet tick {fleet.tick}: live={live} "
              f"queued={sorted(queued)} "
              f"done={ {t: sorted(v) for t, v in done.items() if v} }")
    else:
        for t in sorted(plans):
            for k, s in enumerate(planned[t]):
                went_live = fleet.admit(t, s, priority=len(planned[t]) - k)
                print(f"admit {t}/{s}: "
                      f"{'live' if went_live is not None else 'queued'}")

    rng = np.random.default_rng(args.seed + 1)
    total = sum(len(v) for v in planned.values())
    while sum(len(v) for v in done.values()) < total:
        chunks: dict[str, dict[str, jnp.ndarray]] = {}
        for t, sids in fleet.active_sessions.items():
            store = fleet.group_of(t).engine.store
            for s in sids:
                sig = streams[t][int(s[1:])]
                pos = store.get(f"{t}/{s}").steps
                if pos >= len(sig):
                    continue
                n = args.chunk_len
                if args.ragged:
                    n = int(rng.integers(1, args.chunk_len + 1))
                chunks.setdefault(t, {})[s] = jnp.asarray(
                    sig[pos:pos + n], jnp.float32)
        results = fleet.step(chunks)
        print(f"tick {fleet.tick:3d} | " + " ".join(
            f"{t}:{len(results.get(t, {}))}r q={fleet.queue.depth_of(t)} "
            f"done={len(done[t])}/{len(planned[t])}"
            for t in sorted(plans)))
        for t, sids in list(fleet.active_sessions.items()):
            store = fleet.group_of(t).engine.store
            for s in list(sids):
                if store.get(f"{t}/{s}").steps >= len(streams[t][int(s[1:])]):
                    sess = fleet.close(t, s)
                    done[t].add(s)
                    print(f"  {t}/{s}: served {sess.steps} steps in "
                          f"{sess.chunks} chunks")
        if args.snapshot_dir and fleet.tick % args.snapshot_every == 0:
            path = fleet.snapshot(args.snapshot_dir)
            checkpoint.keep_last(args.snapshot_dir, args.snapshot_keep)
            print(f"  snapshot -> {path}")

    agg = fleet.summarize()
    for t, sub in sorted(agg.get("tenants", {}).items()):
        print(f"{t}: {sub['ticks']} served tick(s) | "
              f"p95 wait {sub['queue_wait_s_p95'] * 1e3:.2f}ms | "
              f"dropped {sub['dropped']}")
    if args.metrics_out:
        fleet.metrics_sink.close()
        print(f"tick metrics -> {args.metrics_out}")


def parse_args(argv=None) -> argparse.Namespace:
    """The launcher's flags (``argv`` None: the command line)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", default=None, metavar="FLEET_JSON",
                    help="multi-tenant fleet mode: serve the tenant table "
                    "in this JSON file through one FleetEngine (see "
                    "load_fleet for the schema); per-model flags below "
                    "are ignored, serving flags (--chunk-len, --ragged, "
                    "--metrics-out, --snapshot-*, --resume) apply")
    ap.add_argument("--sessions", type=int, default=4,
                    help="store capacity: concurrently-live streams")
    ap.add_argument("--overload", type=int, default=None,
                    help="total streams to serve (> --sessions exercises "
                    "the admission queue; default: --sessions)")
    ap.add_argument("--chunk-len", type=int, default=20)
    ap.add_argument("--beats", type=int, default=2,
                    help="ECG beats (T=140 each) per session stream")
    ap.add_argument("--samples", type=int, default=8, help="S MC chains")
    ap.add_argument("--backend", default="pallas_seq",
                    choices=("reference", "pallas_step", "pallas_seq"))
    ap.add_argument("--precision", default=None,
                    choices=("fp32", "bf16", "int8", "int4"),
                    help="serving precision: per-channel weight "
                    "quantization + bf16 activations (default: native "
                    "dtypes).  Snapshots record it; --resume must match.")
    ap.add_argument("--cell", default="lstm", choices=("lstm", "gru"),
                    help="recurrent unit (paper §III-A: GRU drops into the "
                    "same per-gate MCD design; h-only carried state)")
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--placement", default="YNY")
    ap.add_argument("--p", type=float, default=0.125)
    ap.add_argument("--ragged", action="store_true",
                    help="jitter chunk lengths per session per tick")
    ap.add_argument("--capacity", default="fixed",
                    choices=("fixed", "auto", "dynamic"),
                    help="launch-shape policy: fixed=--chunk-len, "
                    "auto=adaptive ladder, dynamic=per-tick max")
    ap.add_argument("--max-pending", type=int, default=256,
                    help="admission-queue backpressure bound")
    ap.add_argument("--shards", type=int, default=0,
                    help="shard every launch over the first N devices "
                    "(batch/data parallel; 0 = no mesh.  Off-TPU, force "
                    "devices with XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--prewarm", action="store_true",
                    help="compile every capacity rung at boot "
                    "(scheduler.prewarm) so no tick pays a first-use "
                    "compile; needs --capacity fixed or auto")
    ap.add_argument("--metrics-out", default=None,
                    help="append per-tick TickMetrics as JSON lines to "
                    "this file (JsonlSink; default: in-memory ring only)")
    ap.add_argument("--controller", action="store_true",
                    help="run the online co-design controller: calibrate "
                    "the roofline against observed ticks and reconfigure "
                    "(S chains, precision) at tick boundaries to hold the "
                    "SLO (repro.serve.controller)")
    ap.add_argument("--slo-p95-ms", type=float, default=50.0,
                    help="SLO: p95 tick latency bound in milliseconds")
    ap.add_argument("--min-tokens-per-sec", type=float, default=0.0,
                    help="SLO: minimum delivered chain-timesteps/sec (p50)")
    ap.add_argument("--min-samples", type=int, default=1,
                    help="uncertainty floor: neither the controller nor "
                    "early exit ever takes a session below this many "
                    "chains")
    ap.add_argument("--early-exit-threshold", type=float, default=None,
                    metavar="DELTA",
                    help="adaptive sampling: retire a session's surplus MC "
                    "chains once halving them would move its uncertainty "
                    "summary by at most DELTA (0.0 = only exactly "
                    "converged; default: off, every session keeps "
                    "--samples chains).  Incompatible with --shards.")
    ap.add_argument("--decisions-out", default=None,
                    help="append controller DecisionRecords as JSON lines "
                    "(default: in-memory ring only)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="durable session snapshots (crash-safe resume)")
    ap.add_argument("--snapshot-every", type=int, default=5,
                    help="snapshot cadence in ticks")
    ap.add_argument("--snapshot-keep", type=int, default=3,
                    help="snapshots retained (older ones pruned; an "
                    "unbounded history would fill the disk on exactly "
                    "the long-running streams snapshots exist for)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest snapshot in --snapshot-dir "
                    "and continue every stream where it left off")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.resume and not args.snapshot_dir:
        ap.error("--resume requires --snapshot-dir")
    if args.early_exit_threshold is not None and args.shards:
        ap.error("--early-exit-threshold is incompatible with --shards "
                 "(sharded launches need uniform chains per session)")
    return args


def _quiet(_msg: str) -> None:
    pass


def build_engine(args, *, log=print) -> StreamingEngine:
    """The single-model engine ``args`` describe, prewarmed if asked.

    ``--shards N`` places it on a data mesh over the first N devices.
    ``log`` None builds silently.
    """
    say = log or _quiet
    cfg = clf.ClassifierConfig(
        hidden=args.hidden, num_layers=args.layers, cell=args.cell,
        mcd=mcd.MCDConfig(p=args.p, placement=args.placement,
                          n_samples=args.samples, seed=args.seed))
    params = clf.init(jax.random.key(args.seed), cfg)
    capacity = {"fixed": args.chunk_len, "auto": "auto",
                "dynamic": None}[args.capacity]
    mesh = None
    if args.shards:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(args.shards)
        say(f"sharding launches over {args.shards} devices (data axis)")
    sink = JsonlSink(args.metrics_out) if args.metrics_out else None
    # The ladder is the operator's launch-shape budget: this launcher never
    # submits chunks longer than --chunk-len, so cap the rungs there (the
    # engine default tops at 512 — pointless compiles for this workload).
    ladder = pow2_ladder(args.chunk_len) if capacity == "auto" else None
    eng = StreamingEngine(params, cfg, backend=args.backend,
                          precision=args.precision,
                          max_sessions=args.sessions,
                          chunk_capacity=capacity, ladder=ladder,
                          max_pending=args.max_pending,
                          mesh=mesh, metrics_sink=sink,
                          early_exit_threshold=args.early_exit_threshold,
                          min_samples=min(args.min_samples, args.samples))
    if args.prewarm:
        t0 = time.perf_counter()
        caps = prewarm(eng)
        say(f"prewarmed capacities {caps} in "
            f"{time.perf_counter() - t0:.2f}s")
    return eng


def open_streams(eng, args, *, log=print):
    """Admit every stream, or with ``--resume`` restore the snapshot.

    Returns ``(streams, labels, done)``: the per-session signals, their
    beat labels, and the sids a resumed run had already finished.
    ``log`` None opens silently.
    """
    say = log or _quiet
    total = args.overload or args.sessions
    # Streams are regenerated deterministically from their generation
    # params; the per-stream cursor lives *in* the session (steps served
    # so far), so a resumed process only needs the snapshot + those params
    # to pick up.  The params ride the snapshot — a resume with different
    # flags would otherwise silently serve different signal content.
    done: set[str] = set()
    if args.resume:
        extra = eng.restore(args.snapshot_dir)
        done = set(extra.get("done", []))
        gen = extra.get("gen")
        if gen and (gen["total"], gen["beats"]) != (total, args.beats):
            say(f"resume: adopting snapshot stream params "
                f"total={gen['total']} beats={gen['beats']} "
                f"(CLI values differ)")
        if gen:
            total, args.beats = int(gen["total"]), int(gen["beats"])
        say(f"resumed tick {eng.tick}: live={eng.active_sessions} "
            f"queued={eng.queued_sessions} done={sorted(done)}")
        # (--seed / --samples mismatches are already rejected by
        # eng.restore: they would change the Bayesian draw itself.)
    streams, labels = build_streams(total, args.beats, args.seed)
    if not args.resume:
        # Admit everything up front: the first --sessions go live, the
        # rest wait in the queue (earlier streams get higher priority —
        # think triage order) and go live as streams finish.
        for k in range(total):
            live = eng.admit(f"ecg-{k}", priority=total - k)
            tag = "live" if live is not None else "queued"
            say(f"admit ecg-{k}: {tag}")
    return streams, labels, done


def serve(eng, streams, labels, args, *, done=None, ctrl=None,
          until_tick: int | None = None, log=print):
    """Serve the admitted streams chunk by chunk until every one is closed.

    Each tick submits the next ``--chunk-len`` steps (jittered with
    ``--ragged``) of every live stream, closes the finished ones (which
    admits queued streams into the freed rows) and snapshots every
    ``--snapshot-every`` ticks when ``--snapshot-dir`` is set.  ``ctrl``
    is an optional :class:`~repro.serve.CoDesignController`, which may
    swap in a reconfigured engine.  ``until_tick`` stops early, once the
    engine's tick counter reaches it.  ``log`` None serves silently (and
    skips the per-tick host reads the progress lines need).

    Returns ``(engine, done, served)``: the engine serving at the end (the
    controller's replacement, if any), the finished sids, and every
    :class:`~repro.serve.stream.ChunkResult` by sid in serving order.
    """
    say = log or _quiet
    total = len(streams)
    done = set() if done is None else done
    served: dict[str, list] = {}
    rng = np.random.default_rng(args.seed + 1)
    while len(done) < total and (until_tick is None
                                 or eng.tick < until_tick):
        chunks = {}
        for sid in eng.active_sessions:
            k = int(sid.split("-")[1])
            pos = eng.store.get(sid).steps
            if pos >= len(streams[k]):
                continue
            n = args.chunk_len
            if args.ragged:
                n = int(rng.integers(1, args.chunk_len + 1))
            chunks[sid] = jnp.asarray(streams[k][pos:pos + n], jnp.float32)
        results = eng.step(chunks)
        for sid, res in results.items():
            served.setdefault(sid, []).append(res)
        if log is not None:
            log(_tick_line(eng, results, args))
        if ctrl is not None:
            rec = ctrl.maybe_reconfigure()
            if rec is not None:
                say(f"  controller[{rec.reason}] applied={rec.applied} "
                    f"winner={rec.winner} "
                    f"p95={rec.observed['duration_s_p95'] * 1e3:.2f}ms")
            eng = ctrl.engine       # maybe a prewarmed replacement

        for sid in list(eng.active_sessions):
            k = int(sid.split("-")[1])
            if eng.store.get(sid).steps >= len(streams[k]):
                sess = eng.close_session(sid)      # frees a row; queue drains
                done.add(sid)
                say(f"{sid}: served {sess.steps} steps in {sess.chunks} "
                    f"chunks (beat labels {labels[k]})")
        if args.snapshot_dir and eng.tick % args.snapshot_every == 0:
            path = eng.snapshot(args.snapshot_dir, extra={
                "done": sorted(done),
                "gen": {"total": total, "beats": args.beats,
                        "seed": args.seed}})
            checkpoint.keep_last(args.snapshot_dir, args.snapshot_keep)
            say(f"  snapshot -> {path}")
    return eng, done, served


def _tick_line(eng, results, args) -> str:
    """One tick's progress line: every served session's class and
    uncertainty, plus the launch shape and queue."""
    line = []
    for sid, res in sorted(results.items()):
        su = res.summary
        cls = int(np.argmax(np.asarray(su.probs)))
        line.append(f"{sid}@{res.steps_total:4d} cls={cls} "
                    f"H={float(su.predictive_entropy):5.3f} "
                    f"MI={float(su.mutual_information):6.4f}")
    m = eng.last_metrics
    stat = (f"cap={m.capacity} q={m.queue_depth} "
            f"waste={m.pad_waste:4.2f}" if m else "idle")
    if m and args.early_exit_threshold is not None:
        stat += f" chains={m.active_chains}"
        if m.reclaimed_rows:
            stat += f" -{m.reclaimed_rows}"
    return f"tick {eng.tick:3d} [{stat}] | " + " | ".join(line)


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    if args.tenants:
        return run_fleet(args)

    eng = build_engine(args)
    ctrl = None
    if args.controller:
        from repro.serve import CoDesignController, SLOPolicy
        slo = SLOPolicy(p95_tick_s=args.slo_p95_ms / 1e3,
                        min_tokens_per_sec=args.min_tokens_per_sec,
                        min_samples=args.min_samples)
        trail = (JsonlSink(args.decisions_out) if args.decisions_out
                 else None)
        ctrl = CoDesignController(eng, slo, decision_sink=trail)
        print(f"controller on: SLO p95<={args.slo_p95_ms}ms "
              f"tokens/s>={args.min_tokens_per_sec} "
              f"S>={args.min_samples} | knobs S{list(ctrl.knobs.samples)}")

    streams, labels, done = open_streams(eng, args)
    cfg = eng.cfg
    print(f"streaming {len(streams)} sessions ({args.sessions} live rows) × "
          f"{args.beats} beats (T={ecg.T_STEPS} each) | S={args.samples} "
          f"chains/session p={cfg.mcd.p} "
          f"B={mcd.placement_str(cfg.mcd.placement)} "
          f"cell={args.cell} backend={args.backend} "
          f"precision={args.precision or 'native'} "
          f"capacity={args.capacity}")
    eng, done, _ = serve(eng, streams, labels, args, done=done, ctrl=ctrl)

    if eng.metrics:
        agg = summarize(eng.metrics)
        print(f"served {sum(m.live_steps for m in eng.metrics)} signal "
              f"steps over {agg['ticks']} ticks | "
              f"capacities used {agg['capacities_used']} | "
              f"pad waste {agg['pad_waste']:4.2f}")
        if args.early_exit_threshold is not None:
            print(f"early exit: {agg['reclaimed_rows']} chain(s) retired | "
                  f"mean active chains {agg['active_chains_mean']:.1f}")
    if ctrl is not None:
        n_applied = sum(1 for r in ctrl.decisions if r.applied)
        print(f"controller: {len(ctrl.decisions)} decision(s), "
              f"{n_applied} applied | final config {ctrl.config}")
        if args.decisions_out:
            ctrl.decision_sink.close()
            print(f"decision trail -> {args.decisions_out}")
    if args.metrics_out:
        eng.metrics_sink.close()
        print(f"tick metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
