"""Streaming engine: unbounded signals, chunk-by-chunk, one batched launch.

The continuous-monitoring counterpart of ``repro.serve.engine``: where the
LM engine serves static batches of prompts, this engine serves *sessions* —
open-ended signals (ECG leads, sensor feeds) that arrive as ragged chunks.
Per tick it

1. collects every submitted chunk, pads them to a common T,
2. folds each session's S MC chains into the batch axis (one weight fetch
   feeds every chain of every session — the paper's sample-wise pipelining,
   now also *session-wise*),
3. resumes each row's carried ``(h, c)`` through the sequence-fused kernel
   in **one ``pallas_seq`` launch per layer**, with per-row ``lengths``
   freezing ragged rows at their own chunk end,
4. emits per-chunk Bayesian uncertainty (``classification_summary`` /
   ``regression_summary``) and stores the new carry.

Bit-exactness contract: streaming passes always supply ``lengths`` (even
when every chunk has the same T).  The lengths-enabled graph family is
bit-identical across launch sizes, chunk splits, batch composition and
backends, so a session's results never depend on how its signal was chunked
or on which other sessions happened to share the batch — the invariant
``tests/test_streaming.py`` pins down.  Masks stay tied across the whole
session via the ``(seed, rows)`` coordinates in ``repro.serve.sessions``.

The control plane (PR 3) sits on top of this data plane: async admission
with priorities and bounded backpressure (``admit``/``repro.serve.
admission``), crash-safe durability (``snapshot``/``restore`` over
``repro.serve.persistence``), and an adaptive launch-shape scheduler with
per-tick metrics (``chunk_capacity="auto"``, ``repro.serve.scheduler``).

The multi-device data plane (PR 5) slots underneath: ``mesh=`` shards
every tick's batch rows over the mesh's data axes with bit-identical
results (``repro.launch.rnn_shardings``), session slots pad to whole
sessions per shard, and per-tick metrics flow through a pluggable
:class:`MetricsSink` (ring buffer by default, JSONL for a durable trail).
"""

from __future__ import annotations

import dataclasses
import functools
import json
from collections import deque
from typing import Any, Mapping, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (autoencoder as _ae, classifier as _clf,
                        distill as _distill, mcd as _mcd)
from repro.kernels import quantize as _quant
from repro.core.uncertainty import (ClassificationSummary, RegressionSummary,
                                    RunningClassificationSummary,
                                    RunningRegressionSummary,
                                    classification_summary,
                                    regression_summary)
from repro.serve import persistence as _persist, spans as _spans
from repro.serve.admission import AdmissionQueue, DrainRejected
from repro.serve.scheduler import AdaptiveTickScheduler, TickMetrics
from repro.serve.sessions import Session, SessionStore


@dataclasses.dataclass
class ChunkResult:
    """Per-chunk Bayesian output for one session."""

    sid: str
    length: int                # timesteps in this chunk
    steps_total: int           # timesteps consumed by the session so far
    summary: Any               # ClassificationSummary | RegressionSummary
                               # (leading batch axis squeezed away)


# ---------------------------------------------------------------------------
# The tick's carry path — one compiled call each way
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _concat_carries(per_session, n_pad: int, part_specs):
    """Batch-aligned layer states from per-session carries, in one program.

    ``per_session[k][layer][part]`` is session ``k``'s ``[S_k, H]`` part;
    ``part_specs[layer]`` gives each part's ``(hidden, dtype)``, which
    sizes the ``n_pad`` zero rows built here.  Concatenation is exact, so
    the result is bit for bit what the per-part eager concatenates gave.
    """
    layers = []
    for li, specs in enumerate(part_specs):
        layer = []
        for pi, (hid, dt) in enumerate(specs):
            acc = [sess[li][pi] for sess in per_session]
            if n_pad:
                acc.append(jnp.zeros((n_pad, hid), dt))
            layer.append(jnp.concatenate(acc))
        layers.append(tuple(layer))
    return layers


@functools.partial(jax.jit, static_argnums=(1,))
def _split_carries(states, counts):
    """Per-session carries sliced out of the launch's layer states.

    Session ``k`` holds the ``counts[k]`` rows after the previous sessions'
    (session-major, chain-minor); the pad rows after them are dropped.
    Returns, per session, the per-layer part tuples ``Session.state`` holds.
    """
    out, off = [], 0
    for si in counts:
        out.append([tuple(part[off:off + si] for part in layer)
                    for layer in states])
        off += si
    return out


# ---------------------------------------------------------------------------
# Metrics sinks — where per-tick observables go
# ---------------------------------------------------------------------------

@runtime_checkable
class MetricsSink(Protocol):
    """Where the engine's per-tick :class:`TickMetrics` go.

    The engine serves *unbounded* streams, so the sink contract is
    explicitly bounded: ``emit`` consumes one record, ``window`` returns
    the recent records the sink still holds (for ``engine.metrics`` /
    ``summarize``) — how many is the sink's policy, not the engine's.
    """

    def emit(self, m: TickMetrics) -> None: ...

    def window(self) -> Sequence[TickMetrics]: ...

    def last(self) -> TickMetrics | None: ...

    def close(self) -> None: ...


class RingBufferSink:
    """Default sink: a bounded in-memory ring (the last ``window`` ticks)."""

    def __init__(self, window: int = 4096):
        self._ring: deque[TickMetrics] = deque(maxlen=int(window))

    def emit(self, m: TickMetrics) -> None:
        self._ring.append(m)

    def window(self) -> list[TickMetrics]:
        return list(self._ring)

    def last(self) -> TickMetrics | None:
        """Newest record, O(1) — serve loops poll this every tick."""
        return self._ring[-1] if self._ring else None

    def close(self) -> None:
        pass


class JsonlSink(RingBufferSink):
    """Append every tick as one JSON line; keeps the ring for ``window()``.

    Every record is flushed as it is written: the JSONL trail is what
    post-mortem SLO analysis reads after a crash, so a killed engine must
    not lose a buffered tail — at most the in-flight line is torn (and an
    operator can ``tail -f`` the file live).  Used by
    ``repro.launch.stream --metrics-out`` and, duck-typed, as the durable
    ``DecisionRecord`` trail of ``repro.serve.controller``.
    """

    def __init__(self, path, *, window: int = 4096):
        super().__init__(window)
        self.path = path
        self._fh = open(path, "a")

    def emit(self, m) -> None:
        super().emit(m)
        self._fh.write(json.dumps(dataclasses.asdict(m)) + "\n")
        self._fh.flush()

    def close(self) -> None:
        self._fh.close()


class StreamingEngine:
    """Stateful session serving for the ECG classifier / autoencoder models.

    Args:
      params: model parameters (``classifier.init`` / ``autoencoder.init``).
      cfg: the matching ``ClassifierConfig`` or ``AutoencoderConfig``; its
        ``mcd`` block fixes S (chains per session), p, placement and seed.
      backend: ``run_stack`` execution path; ``"pallas_seq"`` is the serving
        hot path (weights VMEM-resident across each chunk).
      max_sessions: admission bound on concurrently-open sessions.
      chunk_capacity: when an int, every tick launches with a **fixed
        shape** — chunks pad to this many timesteps and the batch pads to
        ``max_sessions`` session slots (dummy rows, length 1, discarded).
        One jit trace / XLA compile serves every tick, whatever the ragged
        chunk lengths or tick composition; without it each new
        ``(max chunk len, session count)`` pair retraces.  Chunks longer
        than the capacity are rejected.  ``"auto"`` delegates the choice to
        an :class:`AdaptiveTickScheduler` — per tick the launch T is picked
        from a small ladder of pre-warmable shapes tracking the observed
        chunk-length distribution (compiles bounded by the ladder length;
        batch still pads to ``max_sessions``).  All three policies are
        bit-identical: the lengths-pinned graph family doesn't care about
        launch shape.
      max_pending: admission-queue bound (``admit`` backpressure).
      ladder: capacity candidates for ``chunk_capacity="auto"`` (default:
        powers of two up to 512, see ``scheduler.pow2_ladder``).
      metrics_window: ring size of the default metrics sink (and the
        ``dropped_admissions`` bound) — bounded, the engine targets
        unbounded streams.
      metrics_sink: where per-tick :class:`TickMetrics` go (a
        :class:`MetricsSink`; default: ``RingBufferSink(metrics_window)``).
        ``engine.metrics`` reads the sink's window, so ``JsonlSink`` keeps
        the in-process observables *and* a durable JSONL trail.
      mesh, policy: shard every launch over the mesh's data axes
        (``repro.launch.rnn_shardings``).  The engine becomes placement-
        aware: session slots pad to a whole number per shard so each
        device serves complete sessions (all S chains of a session land
        on one shard), while mask rows stay *global* coordinates — which
        is exactly why snapshots remain host-portable: a snapshot taken
        on an 8-device mesh restores bit-identically onto 1 device (or
        any other mesh shape), because nothing device-shaped is ever part
        of the Bayesian draw or the carry.
      precision: serving precision (``repro.kernels.quantize.PRECISIONS``;
        None = native dtypes).  Quantized/cast in-graph from the fp32
        master params every launch — ``params`` and training checkpoints
        are untouched.  The carry dtypes follow the precision (h in the
        activation dtype, LSTM c in fp32), so snapshots record it and
        :meth:`restore` refuses a mismatch — resuming bf16 carries into
        an fp32 engine would silently change the stream's numerics.
      early_exit_threshold: enable staged early-exit MC sampling.  After
        each served chunk the engine compares a session's uncertainty
        summary over *all* its chains against the summary over the first
        half (the incremental ``Running*Summary`` accumulators in
        ``repro.core.uncertainty``): a prefix-converged session —
        classification: ``|MI_full - MI_half|``; autoencoder: mean
        ``|epistemic_full - epistemic_half|`` — has its surplus chains
        retired to ``max(min_samples, ceil(s/2))``, one stage per tick.
        Retirement keeps a chain *prefix*, so surviving chains' masks and
        carries are untouched and co-batched neighbours are unaffected
        (masks stay pure functions of ``(seed, rows)``).  ``None``
        (default) disables the estimator entirely — the engine is then
        bit-identical to the pre-dynamic-S static engine on every
        backend, cell, chunking and snapshot path.  Incompatible with
        ``mesh`` (ragged chain counts would unbalance the shards).
      min_samples: the early-exit floor — no session is ever retired
        below this many chains (the ``SLOPolicy.min_samples`` uncertainty
        floor, enforced in the data plane).
      student: distilled student heads (``repro.core.distill.init_student``)
        enabling ``mode="student"`` sessions — one deterministic row
        (``STUDENT_ROW_FLAG``: the kernels skip its masks in-register)
        co-batched in the same per-layer launches as the MC chains, decoded
        through the student heads instead of the chain-axis estimator.
        None (default): student admissions are refused and the engine is
        bit-identical to the pre-distill engine.  Incompatible with
        ``mesh`` (single-row sessions would break the whole-sessions-per-
        shard placement).
      student_escalate_threshold: MC fallback trigger.  After each served
        chunk, a student session whose *predicted* epistemic uncertainty
        (classifier: MI nats; autoencoder: mean epistemic variance) exceeds
        this value escalates: ``SessionStore.grow`` retires the student row
        and regrows ``n_samples`` fresh MC chains from the student's carry,
        so from the next chunk the session runs the full Bayesian
        estimator.  Fresh rows mean no mask reuse — the escalated session
        is bit-identical to an always-MC session attached at that carry.
        None: students never escalate (still serviceable via an explicit
        ``store.grow``).

    The Pallas backends lower natively on a TPU backend and run in the
    Pallas interpreter elsewhere (:func:`repro.kernels.resolve_interpret`).
    """

    def __init__(self, params, cfg, *, backend: str = "pallas_seq",
                 max_sessions: int = 64,
                 chunk_capacity: int | str | None = None,
                 max_pending: int = 256, ladder=None,
                 scheduler: AdaptiveTickScheduler | None = None,
                 metrics_window: int = 4096,
                 metrics_sink: MetricsSink | None = None,
                 mesh=None, policy=None, precision: str | None = None,
                 early_exit_threshold: float | None = None,
                 min_samples: int = 1,
                 student=None,
                 student_escalate_threshold: float | None = None):
        if isinstance(cfg, _clf.ClassifierConfig):
            self.kind = "classifier"
        elif isinstance(cfg, _ae.AutoencoderConfig):
            self.kind = "autoencoder"
        else:
            raise TypeError(f"unsupported config type {type(cfg).__name__}")
        self.params = params
        self.cfg = cfg
        self.backend = backend
        if precision is not None:
            _quant.check_precision(precision)
        self.precision = precision
        self.chunk_capacity = chunk_capacity
        self.max_sessions = max_sessions
        self.mesh = mesh
        self.policy = policy
        if mesh is not None:
            # deferred: serve must import without the launch layer
            from repro.launch import rnn_shardings as _rs
            self._shards = _rs.data_size(mesh, policy or _rs.DEFAULT_POLICY)
        else:
            self._shards = 1
        self._scheduler = None
        if chunk_capacity == "auto":
            # A caller-tuned scheduler (percentile, window) wins over the
            # default ladder-only construction.
            self._scheduler = scheduler or AdaptiveTickScheduler(ladder)
        elif isinstance(chunk_capacity, str):
            raise ValueError(f"chunk_capacity must be an int, None or "
                             f"'auto', got {chunk_capacity!r}")
        # Fixed-shape launches (idle session slots padded) for both the
        # hand-set capacity and the adaptive ladder — one graph per shape.
        self._fixed = chunk_capacity is not None
        # Recurrent cell type drives the carry pytree arity: LSTM sessions
        # store per-layer (h, c), GRU sessions (h,) — see _gather_states.
        self.cell = getattr(cfg, "cell", "lstm")
        s = cfg.mcd.n_samples if cfg.mcd.any_bayesian else 1
        # The engine-wide chain *ceiling*.  S itself is per-session state
        # (SessionStore): admissions may open below the ceiling and early
        # exit retires chains mid-stream, so launch shapes are sized by the
        # ceiling while live chain counts drift underneath it.
        self.n_samples = max(1, s)
        if early_exit_threshold is not None:
            if mesh is not None:
                raise ValueError(
                    "early_exit_threshold is incompatible with mesh= — "
                    "ragged per-session chain counts would unbalance the "
                    "whole-sessions-per-shard placement; run early exit "
                    "unsharded or disable it on the mesh engine")
            if not float(early_exit_threshold) >= 0.0:
                raise ValueError(f"early_exit_threshold must be >= 0, "
                                 f"got {early_exit_threshold}")
        self.early_exit_threshold = (None if early_exit_threshold is None
                                     else float(early_exit_threshold))
        if not 1 <= int(min_samples) <= self.n_samples:
            raise ValueError(
                f"min_samples must be in [1, {self.n_samples}], "
                f"got {min_samples}")
        self.min_samples = int(min_samples)
        if student is not None and mesh is not None:
            raise ValueError(
                "student= is incompatible with mesh= — single-row student "
                "sessions would break the whole-sessions-per-shard "
                "placement; serve the distilled fast path unsharded")
        self.student = student
        if student_escalate_threshold is not None:
            if student is None:
                raise ValueError("student_escalate_threshold needs student= "
                                 "heads — there is nothing to escalate from")
            if not float(student_escalate_threshold) >= 0.0:
                raise ValueError(
                    f"student_escalate_threshold must be >= 0, "
                    f"got {student_escalate_threshold}")
        self.student_escalate_threshold = (
            None if student_escalate_threshold is None
            else float(student_escalate_threshold))
        self.store = SessionStore(self.n_samples, cfg.mcd.seed,
                                  max_sessions=max_sessions)
        # Per-tick attribution for the fleet sink: sid -> chains served /
        # rows retired / student rows / escalations on the most recent
        # step() (read by FleetEngine to split the tick-level counts
        # across tenant records).
        self._last_served_chains: dict[str, int] = {}
        self._last_reclaimed: dict[str, int] = {}
        self._last_student_rows: dict[str, int] = {}
        self._last_escalated: dict[str, int] = {}
        self.queue = AdmissionQueue(max_pending)
        self.tick = 0
        # Pluggable, bounded: the engine is built for unbounded streams —
        # an ever-growing per-tick list would leak on exactly that
        # workload.  summarize() rolls up whatever the sink's window holds.
        self.metrics_sink: MetricsSink = (metrics_sink
                                          or RingBufferSink(metrics_window))
        # Tickets the store refused mid-drain ((Ticket, error) pairs, newest
        # last).  A drain rejection concerns the ticket's *owner*, not
        # whichever caller happened to trigger the drain — see _drain.
        self.dropped_admissions: deque = deque(maxlen=metrics_window)
        # Drops not yet surfaced in a TickMetrics record.  The deque above
        # is in-memory only; the metrics trail is the durable record, so
        # every drop — whether it happened inside step()'s drain or between
        # ticks in admit()/close_session() — lands in the next tick's
        # ``dropped`` count.
        self._dropped_unreported = 0
        # The carry path's compiled layouts (see _carry_call) and the zero
        # carries fresh sessions pass, by (rows, hidden, dtype).
        self._carry_layouts: set = set()
        self._carry_layouts_new = 0
        self._zero_carries: dict = {}

    # -- session lifecycle ---------------------------------------------------
    def open_session(self, sid: str, *, n_samples: int | None = None,
                     mode: str = "mc"):
        """Admit a stream *now* or fail fast with ``CapacityError``.

        The synchronous path — callers that would rather wait for a freed
        row than handle the error use :meth:`admit`.  Its mask rows are
        fixed here, for life; ``n_samples`` opens below the engine ceiling
        (None: the ceiling).  ``mode="student"`` opens on the distilled
        fast path (one deterministic row; needs ``student=`` heads).
        """
        if mode == "student":
            self._check_student(sid)
        else:
            self._check_chain_count(sid, n_samples)
        return self.store.admit(sid, n_samples=n_samples, mode=mode)

    def _check_student(self, sid: str) -> None:
        if self.student is None:
            raise ValueError(
                f"session {sid!r}: mode='student' needs an engine built "
                "with student= head params (repro.core.distill)")

    def _check_chain_count(self, sid: str, n_samples: int | None) -> None:
        # Sharded engines place whole sessions per shard assuming one S —
        # refuse a sub-ceiling admission up front rather than poisoning the
        # tick every co-batched session shares (see step()'s guard).
        if (n_samples is not None and self._shards > 1
                and int(n_samples) != self.n_samples):
            raise ValueError(
                f"session {sid!r}: sharded engines serve a uniform "
                f"{self.n_samples} chains/session; per-session S needs an "
                "unsharded engine")

    def admit(self, sid: str, *, priority: int = 0,
              session: Session | None = None,
              n_samples: int | None = None,
              mode: str | None = None):
        """Queue a stream for admission; drain it into any free row now.

        The asynchronous path: never raises ``CapacityError`` — at capacity
        the request waits (bounded by ``max_pending``; ``QueueFull`` beyond
        that) and goes live when an eviction or tick boundary frees a row,
        highest ``priority`` first, FIFO within a class.  ``session`` makes
        it a re-attach request (an evicted carry resumes the same draw).
        ``mode="student"`` queues a distilled fast-path admission.
        Returns the live :class:`Session` if admitted immediately, else
        None (it is queued; watch ``queued_sessions``).
        """
        if mode == "student":
            self._check_student(sid)
        if sid in self.store:
            raise ValueError(f"session {sid!r} already admitted")
        if session is not None:
            # Fail the statically-checkable mismatches *here*, not later
            # inside whichever step()/close_session() happens to drain the
            # ticket (where the error would hit an unrelated caller and,
            # in close_session, cost them the evicted carry).
            if session.seed != self.store.seed:
                raise ValueError(
                    f"session {sid!r} was drawn under seed "
                    f"{session.seed!r}, engine uses {self.store.seed!r}")
            if int(session.rows.shape[0]) > self.n_samples:
                raise ValueError(
                    f"session {sid!r} carries {int(session.rows.shape[0])} "
                    f"MC chains, engine ceiling is {self.n_samples}")
            if (self._shards > 1
                    and int(session.rows.shape[0]) != self.n_samples):
                self._check_chain_count(sid, int(session.rows.shape[0]))
            if session.mode == "student":
                self._check_student(sid)
        elif mode != "student":
            self._check_chain_count(sid, n_samples)
        self.queue.submit(sid, priority=priority, session=session,
                          n_samples=n_samples, mode=mode)
        try:
            self.queue.drain(self.store)
        except DrainRejected as err:
            # The caller is synchronously present for *its own* ticket: if
            # the drain rejected it (e.g. a row collision only the store
            # can detect), re-raise rather than return the None that means
            # "queued" — the ticket is gone and would never go live.
            # Other sessions' poison is contained as in _drain.
            mine = next((e for t, e in err.rejected if t.sid == sid), None)
            others = [(t, e) for t, e in err.rejected if t.sid != sid]
            self.dropped_admissions.extend(others)
            self._dropped_unreported += len(others)
            if mine is not None:
                raise mine from err
        live = self.store
        return live.get(sid) if sid in live else None

    def close_session(self, sid: str):
        """Evict a finished stream; returns the Session (final carry).

        The freed row is immediately offered to the admission queue.
        """
        sess = self.store.evict(sid)
        self._drain()
        return sess

    def attach_session(self, session):
        """Re-admit an evicted Session (same draw: state + (seed, rows))."""
        if session.mode == "student":
            self._check_student(session.sid)
        else:
            self._check_chain_count(session.sid, int(session.rows.shape[0]))
        return self.store.attach(session)

    def _drain(self):
        # DrainRejected stops at this layer: the poison is some *other*
        # session's ticket, and raising here would fail an unrelated caller
        # — close_session would lose the evicted carry it must return, a
        # successful admit() would look failed, step() would drop its tick.
        # The drain already completed (healthy tickets went live); record
        # the rejects for the operator and keep serving.
        try:
            return self.queue.drain(self.store)
        except DrainRejected as err:
            self.dropped_admissions.extend(err.rejected)
            self._dropped_unreported += len(err.rejected)
            return err.admitted

    @property
    def active_sessions(self) -> list[str]:
        return self.store.active

    @property
    def queued_sessions(self) -> list[str]:
        """Sids still waiting for a row, in drain order."""
        return [t.sid for t in self.queue.waiting()]

    @property
    def metrics(self) -> Sequence[TickMetrics]:
        """The metrics sink's retained window (recent ticks, oldest first)."""
        return self.metrics_sink.window()

    @property
    def last_metrics(self) -> TickMetrics | None:
        return self.metrics_sink.last()

    # -- durability ----------------------------------------------------------
    def snapshot(self, directory: str, *, step: int | None = None,
                 extra: dict | None = None) -> str:
        """Atomic, crash-safe snapshot of every live + queued stream.

        Durable state is exactly: per-session per-chain ``(h, c)`` carries,
        ``(seed, rows)`` mask coordinates, step/chunk cursors, the row
        allocator, the admission wait-list, the scheduler's observation
        window and the tick counter.  Masks themselves are *not* stored —
        the counter PRNG recomputes them from ``(seed, rows)``, which is
        why restore is bit-exact.  Model params ride the training
        checkpoint, not the session snapshot.
        """
        return _persist.snapshot_store(directory, self.store, step=step,
                                       queue=self.queue,
                                       extra=self._engine_meta(extra))

    def _engine_meta(self, extra: dict | None = None) -> dict:
        """The per-engine snapshot meta — validated by :meth:`restore`.

        Factored out so a :class:`~repro.serve.fleet.FleetEngine` snapshot
        can embed one of these per launch group under a single atomic
        manifest and reuse the exact same restore-time validation.
        """
        engine_meta = {"tick": self.tick, "kind": self.kind,
                       "backend": self.backend, "cell": self.cell,
                       # Validated on restore: the carry dtypes (h in the
                       # activation dtype, LSTM c fp32) follow the serving
                       # precision, so the stream is only resumable under
                       # the precision that produced it.
                       "precision": self.precision,
                       # Observability only — deliberately NOT validated on
                       # restore: a snapshot is host-portable and restores
                       # onto any mesh shape (mask rows are global, carries
                       # are device-free host arrays).
                       "data_shards": self._shards,
                       "mcd": {"p": float(self.cfg.mcd.p),
                               "placement":
                                   _mcd.placement_str(self.cfg.mcd.placement)}}
        if self._scheduler is not None:
            engine_meta["sched"] = self._scheduler.state()
        if extra is not None:
            engine_meta["extra"] = extra
        return engine_meta

    def restore(self, directory: str, *, step: int | None = None,
                sids: list[str] | None = None) -> dict:
        """Resume every snapshotted stream into this (fresh) engine.

        Replaces the store, wait-list and tick counter with the snapshot's;
        serving then continues bit-identically to the uninterrupted run
        (any backend, any ``chunk_capacity`` — including one different
        from the snapshotting process's).  Returns the engine ``extra``
        meta stashed by :meth:`snapshot`.  The engine must be freshly
        constructed (no live sessions) with a matching model config.
        """
        if self.store.sessions() or len(self.queue):
            raise RuntimeError("restore() needs a fresh engine: live or "
                               "queued sessions would collide")
        # Size the replacement queue to hold the snapshot's whole wait-list
        # — a valid snapshot must restore even if this process was launched
        # with a smaller max_pending than the one that wrote it.
        peek = _persist.load_snapshot_meta(directory, step)
        queue = AdmissionQueue(max(self.queue.max_pending,
                                   len(peek["queue"]) or 1))
        store, meta = _persist.restore_store(
            directory, step=peek["step"], sids=sids, queue=queue,
            max_sessions=self.max_sessions)
        engine_meta = self._check_restore_meta(meta)
        self._adopt(store, queue, engine_meta)
        return engine_meta.get("extra", {})

    def _check_restore_meta(self, meta: dict) -> dict:
        """Validate snapshot meta against this engine; return its engine meta.

        Shared by :meth:`restore` and the fleet restore path — every typed
        mismatch error below fires identically whether the snapshot is a
        standalone engine's or one launch group inside a fleet manifest.
        """
        # The snapshot records the writing store's chain *ceiling*; sessions
        # carry their own S in their rows arrays (pre-dynamic snapshots
        # simply have every session at the old uniform S).  The ceilings
        # must match exactly: it pins the row-allocator layout, and a
        # mismatch is a config mixup, not a resumable state.
        if meta["n_samples"] != self.n_samples:
            raise ValueError(
                f"snapshot's chain ceiling is {meta['n_samples']} MC "
                f"chains/session, engine ceiling is {self.n_samples}")
        if meta["seed"] != self.cfg.mcd.seed:
            raise ValueError(
                f"snapshot drawn under seed {meta['seed']!r}, engine uses "
                f"{self.cfg.mcd.seed!r} — resuming would change the masks")
        engine_meta = meta.get("extra") or {}
        if engine_meta.get("kind") not in (None, self.kind):
            raise ValueError(f"snapshot is a {engine_meta['kind']} stream, "
                             f"engine is a {self.kind}")
        # The carry pytree arity follows the cell — resuming LSTM (h, c)
        # carries into a GRU engine (or vice versa) could only mis-structure
        # the states (and the mask gate count differs anyway).
        snap_cell = engine_meta.get("cell", "lstm")
        if snap_cell != self.cell:
            raise ValueError(f"snapshot streamed through a {snap_cell} "
                             f"stack, engine runs {self.cell} — the carries "
                             "are not interchangeable")
        # The carry dtypes follow the serving precision (h in the
        # activation dtype, LSTM c fp32) — resuming across a precision
        # change would mix dtypes mid-stream and silently change the
        # numerics.  Pre-quantization snapshots carry no key: they were
        # written by native-dtype engines, so they restore only into one
        # (precision=None), which is exactly what get() defaults to.
        snap_prec = engine_meta.get("precision")
        if snap_prec != self.precision:
            raise ValueError(
                f"snapshot streamed at precision {snap_prec!r}, engine "
                f"serves {self.precision!r} — the carries are not "
                "interchangeable")
        # p/placement change the mask *values* even under the same (seed,
        # rows) — resuming across them would silently alter the draw.
        snap_mcd = engine_meta.get("mcd")
        here_mcd = {"p": float(self.cfg.mcd.p),
                    "placement": _mcd.placement_str(self.cfg.mcd.placement)}
        if snap_mcd is not None and snap_mcd != here_mcd:
            raise ValueError(
                f"snapshot streamed under mcd {snap_mcd}, engine uses "
                f"{here_mcd} — resuming would silently change the masks")
        return engine_meta

    def _adopt(self, store: SessionStore, queue: AdmissionQueue,
               engine_meta: dict) -> None:
        """Take over a restored store/queue + validated engine meta."""
        # A student session decodes through the student heads — adopting
        # one into an engine that has none would silently misserve it
        # (pre-distill snapshots carry no modes and restore everywhere).
        if self.student is None:
            stu = ([s.sid for s in store.sessions() if s.mode == "student"]
                   + [t.sid for t in queue.waiting()
                      if getattr(t, "mode", None) == "student"])
            if stu:
                raise ValueError(
                    f"snapshot carries student-mode sessions {sorted(stu)}; "
                    "this engine was built without student= heads")
        # The engine's own ceiling governs from here on (meta check pinned
        # them equal) — restored sessions keep whatever per-session S their
        # rows arrays carry.
        store.n_samples = self.n_samples
        self.store = store
        self.queue = queue
        self.tick = int(engine_meta.get("tick", 0))
        if self._scheduler is not None and "sched" in engine_meta:
            self._scheduler.load_state(engine_meta["sched"])

    # -- serving -------------------------------------------------------------
    def step(self, chunks: Mapping[str, Any]) -> dict[str, ChunkResult]:
        """Serve one chunk per submitting session, in one batched pass.

        ``chunks`` maps session id → ``[t, input_dim]`` (or ``[t]`` when
        ``input_dim == 1``) signal slices; ``t`` may differ per session
        (ragged) and must be >= 1.  Every listed session must be open.
        Returns per-session :class:`ChunkResult`; carried state advances.

        The call is one ``engine.step`` span and its phases are child spans
        (:mod:`repro.serve.spans`); the tick's :class:`TickMetrics` carries
        their host times in ``phase_s``, with the compiles and GC pauses
        that fell inside it.
        """
        with _spans.tick(self.tick) as rec:
            with rec.phase("engine.drain"):
                self._drain()  # tick boundary: freed rows feed the wait-list
            if not chunks:
                return {}
            results, counts = self._serve(chunks, rec)
        # Host time of step(), drain included.  The device work it
        # dispatched may still run when it returns: it ends where the
        # caller fetches the results.
        dur = rec.duration_s
        m = TickMetrics(
            tick=self.tick, **counts, queue_depth=len(self.queue),
            duration_s=dur,
            tokens_per_sec=(counts["live_chain_steps"] / dur
                            if dur > 0 else 0.0),
            shards=self._shards, compiles=rec.compiles,
            phase_s=rec.phase_s, gc_s=rec.gc_s,
            dropped=self._take_dropped(),
            active_chains=self.store.active_chains)
        self.metrics_sink.emit(m)
        self.tick += 1
        return results

    def _serve(self, chunks, rec) -> tuple[dict[str, ChunkResult], dict]:
        """The body of :meth:`step` after the drain, phase by phase, each
        phase a span of the tick's record ``rec``.

        Returns the results and the tick's counts for its
        :class:`TickMetrics`.
        """
        # Head-of-line admission delay *after* the drain: how long the
        # oldest stream that still couldn't get a row has been waiting.
        queue_wait_s = self.queue.oldest_wait_s()
        self._carry_layouts_new = 0
        with rec.phase("engine.stage"):
            sessions, xs, lens = [], [], []
            for sid, chunk in chunks.items():
                sess = self.store.get(sid)
                x = np.asarray(chunk)
                if x.ndim == 1:
                    x = x[:, None]
                if x.ndim != 2 or x.shape[0] < 1:
                    raise ValueError(f"chunk for {sid!r} must be [t>=1, "
                                     f"input_dim], got shape {tuple(x.shape)}")
                sessions.append(sess)
                xs.append(x)
                lens.append(x.shape[0])
            # Per-session chain counts — S is session state, not an engine
            # constant.  With every session at the ceiling (the threshold-off
            # default) the layout below is byte-identical to the static-S
            # engine's; sharded launches require exactly that (whole sessions
            # per shard is only well-defined with one S).
            s_list = [int(sess.rows.shape[0]) for sess in sessions]
            if self._shards > 1 and any(si != self.n_samples
                                        for si in s_list):
                raise ValueError(
                    "sharded launches need every session at the engine "
                    f"ceiling ({self.n_samples} chains); got {s_list} — "
                    "per-session S would straddle shard boundaries")

            if self._scheduler is not None:
                t_max = self._scheduler.plan(lens)
            elif self.chunk_capacity is not None:
                if max(lens) > self.chunk_capacity:
                    raise ValueError(f"chunk of {max(lens)} steps exceeds "
                                     f"chunk_capacity={self.chunk_capacity}")
                t_max = self.chunk_capacity
            else:
                t_max = max(lens)
            dtype = xs[0].dtype
            slots = self._slot_count(len(sessions))
            # Launch size: fixed-shape modes always budget ceiling chains per
            # slot — retired chains become tail padding and the one-graph
            # guarantee survives early exit.  Dynamic mode launches exactly the
            # live chains, so retirement shrinks the actual compute.
            live_chains = sum(s_list)
            nb = slots * self.n_samples if (self._fixed or self._shards > 1) \
                else live_chains
            n_pad = nb - live_chains
            # Batch assembly stages in host numpy — one device transfer per
            # operand per tick, not O(sessions) tiny dispatches.
            # Session-major, chain-minor: session k's chains pack at
            # offsets[k], matching the concatenated per-session mask rows
            # (offset k*S when uniform).
            x_host = np.zeros((nb, t_max, xs[0].shape[1]), dtype)
            rows_host = np.zeros((nb,), np.uint32)
            lens_host = np.ones((nb,), np.int32)
            offsets, off = [], 0
            for x, L, sess, si in zip(xs, lens, sessions, s_list):
                sl = slice(off, off + si)
                offsets.append(off)
                x_host[sl, :L] = x[None]
                rows_host[sl] = np.asarray(sess.rows)
                lens_host[sl] = L
                off += si
            x_batch = jnp.asarray(x_host)
            rows = jnp.asarray(rows_host)
            lengths = jnp.asarray(lens_host)
        with rec.phase("engine.carry_gather"):
            initial_state = self._gather_states(sessions, dtype, n_pad)

        with rec.phase("engine.launch"):
            outs, states = self._apply(x_batch, rows, lengths, initial_state)
            if self._shards > 1:
                # The summaries below run op by op; on a sharded array XLA
                # partitions each op and may round a reduction differently
                # from one device (ulp-level on a TPU).  The outputs are
                # small, so gather them onto one device: sharded ==
                # unsharded, bit for bit.
                dev = self.mesh.devices.flat[0]
                outs = tuple(None if o is None else jax.device_put(o, dev)
                             for o in outs)
            if self.kind == "classifier":
                (logits,) = outs
            else:
                mean, log_var, dec_out = outs

        with rec.phase("engine.summarize"):
            # Batched summaries over [s, group, ...] — per-session results
            # are indexed out, not recomputed per session.  A uniform tick
            # (the common case, and always when the threshold is off) is one
            # reshape of the contiguous live prefix — the static engine's
            # exact op sequence.  Ragged ticks group sessions by chain count
            # (staged halving keeps distinct counts at most log2(S)+1) and
            # gather each group's rows; values are launch-layout-invariant
            # either way.
            # Student sessions sit outside the chain-axis estimator entirely:
            # their single deterministic row is decoded through the student
            # heads below, and only the MC sessions group.
            k_n = len(sessions)
            summaries: list = [None] * k_n
            stu_ks = [k for k in range(k_n) if sessions[k].mode == "student"]
            mc_ks = [k for k in range(k_n) if sessions[k].mode != "student"]
            mc_s = [s_list[k] for k in mc_ks]
            groups = ([(s_list[0], list(range(k_n)))]
                      if not stu_ks and len(set(s_list)) == 1
                      else sorted({si: [k for k in mc_ks if s_list[k] == si]
                                   for si in set(mc_s)}.items()))
            for si, ks in groups:
                if len(ks) == k_n:
                    sel = lambda a: a.reshape((-1, si) + a.shape[1:])[:k_n]  # noqa: E731
                else:
                    idx = jnp.asarray(np.concatenate(
                        [np.arange(offsets[k], offsets[k] + si) for k in ks]))
                    sel = lambda a: a[idx].reshape((len(ks), si) + a.shape[1:])  # noqa: E731
                if self.kind == "classifier":
                    per_chain = jnp.swapaxes(sel(logits), 0, 1)
                    batched = classification_summary(
                        per_chain.astype(jnp.float32))
                    for j, k in enumerate(ks):
                        summaries[k] = ClassificationSummary(
                            *(v[j] for v in batched))
                else:
                    mu = jnp.swapaxes(sel(mean), 0, 1)
                    lv = (None if log_var is None
                          else jnp.swapaxes(sel(log_var), 0, 1))
                    batched = regression_summary(
                        mu.astype(jnp.float32),
                        None if lv is None else lv.astype(jnp.float32))
                    for j, k in enumerate(ks):
                        summaries[k] = RegressionSummary(
                            *(v[j] for v in batched))

            # Distilled fast path: a student session's summary comes from the
            # student heads on its one deterministic row's features — h_T for
            # the classifier, the decoder hidden sequence for the autoencoder.
            # One batched head call over every student row, indexed out like
            # the MC groups — per-session calls would put O(sessions) tiny
            # dispatches back on the tick.
            if stu_ks:
                idx = jnp.asarray([offsets[k] for k in stu_ks])
                if self.kind == "classifier":
                    batched = _distill.classifier_student_summary(
                        self.student, states[-1][0][idx])
                else:
                    batched = _distill.autoencoder_student_summary(
                        self.student, dec_out[idx],
                        getattr(self.cfg, "heteroscedastic", True))
                for j, k in enumerate(stu_ks):
                    summaries[k] = type(batched)(*(v[j] for v in batched))

        with rec.phase("engine.writeback"):
            # Windowed-decoder AEs reconstruct only min(L, W) positions per
            # chunk — the valid slice is capped by the decode window, not the
            # chunk.
            win = getattr(self.cfg, "decode_window", None)
            carries = self._carry_call(_split_carries, states, tuple(s_list))
            results: dict[str, ChunkResult] = {}
            for k, (sess, L) in enumerate(zip(sessions, lens)):
                if self.kind == "classifier":
                    summary = summaries[k]
                else:
                    valid = L if win is None else min(L, win)
                    summary = RegressionSummary(
                        *(v[:valid] for v in summaries[k]))
                sess.state = carries[k]
                sess.steps += L
                sess.chunks += 1
                results[sess.sid] = ChunkResult(sid=sess.sid, length=L,
                                                steps_total=sess.steps,
                                                summary=summary)

            self._last_served_chains = {sess.sid: si for sess, si
                                        in zip(sessions, s_list)}
            self._last_student_rows = {sessions[k].sid: 1 for k in stu_ks}
        with rec.phase("engine.early_exit"):
            reclaimed = self._early_exit(sessions, lens, s_list, offsets,
                                         outs, win)
        # Escalation runs *after* state writeback: grow() tiles the carry
        # the tick just stored, so the regrown chains resume exactly the
        # student's post-chunk state.
        with rec.phase("engine.escalate"):
            escalations = self._escalate(sessions, results)

        live_chain_steps = int(sum(L * si for L, si in zip(lens, s_list)))
        counts = dict(
            capacity=int(t_max), n_chunks=len(sessions),
            live_rows=live_chains, batch_rows=nb,
            live_steps=int(sum(lens)), live_chain_steps=live_chain_steps,
            padded_steps=nb * int(t_max),
            pad_waste=1.0 - live_chain_steps / (nb * int(t_max)),
            queue_wait_s=queue_wait_s, reclaimed_rows=reclaimed,
            student_rows=len(stu_ks), escalations=escalations,
            carry_layouts_new=self._carry_layouts_new)
        return results, counts

    def _early_exit(self, sessions, lens, s_list, offsets, outs, win) -> int:
        """Retire surplus chains of prefix-converged sessions (one stage).

        For each served session still above the floor, compare the
        uncertainty summary over the prefix it would keep
        (``max(min_samples, ceil(s/2))`` chains) against the summary over
        all its chains, via the incremental accumulators — classification:
        ``|MI_full - MI_prefix|``; autoencoder: mean
        ``|epistemic_full - epistemic_prefix|`` over the valid positions.
        A delta at or under the threshold halves the session (down to the
        floor) through ``SessionStore.retire`` — prefix-trim only, so the
        survivors' masks/carries and every co-batched neighbour are
        untouched.  Returns total rows retired this tick.
        """
        self._last_reclaimed = {}
        if self.early_exit_threshold is None:
            return 0
        reclaimed = 0
        for k, (sess, L) in enumerate(zip(sessions, lens)):
            si = s_list[k]
            keep = max(self.min_samples, (si + 1) // 2)
            if keep >= si:
                continue
            off = offsets[k]
            if self.kind == "classifier":
                (logits,) = outs
                lg = np.asarray(logits[off:off + si])[:, None, :]  # [s,1,C]
                prefix = RunningClassificationSummary().update(lg[:keep])
                full = prefix.copy().update(lg[keep:])
                delta = float(np.abs(
                    np.asarray(full.finalize().mutual_information)
                    - np.asarray(prefix.finalize().mutual_information))[0])
            else:
                mean, log_var = outs[0], outs[1]
                valid = L if win is None else min(L, win)
                mu = np.asarray(mean[off:off + si, :valid])
                lv = (None if log_var is None
                      else np.asarray(log_var[off:off + si, :valid]))
                prefix = RunningRegressionSummary().update(
                    mu[:keep], None if lv is None else lv[:keep])
                full = prefix.copy().update(
                    mu[keep:], None if lv is None else lv[keep:])
                delta = float(np.mean(np.abs(
                    np.asarray(full.finalize().epistemic)
                    - np.asarray(prefix.finalize().epistemic))))
            if delta <= self.early_exit_threshold:
                n_ret = self.store.retire(sess.sid, keep)
                if n_ret:
                    reclaimed += n_ret
                    self._last_reclaimed[sess.sid] = n_ret
        return reclaimed

    def _escalate(self, sessions, results) -> int:
        """Regrow student sessions whose predicted uncertainty crossed the
        threshold (the MC fallback).

        Reads each student session's *served* summary — the student heads'
        predicted MI (classifier) / mean epistemic variance (autoencoder) —
        and a strict ``>`` compare against ``student_escalate_threshold``
        triggers ``SessionStore.grow(sid, n_samples)``: the det row retires
        and the engine-ceiling count of fresh MC chains resumes the tiled
        carry.  From the next chunk the session is indistinguishable from
        an always-MC session attached at that carry (fresh rows ⇒ fresh
        masks; pinned bit-identical in tests).  Returns escalation count.
        """
        self._last_escalated = {}
        if self.student_escalate_threshold is None:
            return 0
        n = 0
        for sess in sessions:
            if sess.mode != "student":
                continue
            summ = results[sess.sid].summary
            if self.kind == "classifier":
                u = float(np.asarray(summ.mutual_information))
            else:
                u = float(np.mean(np.asarray(summ.epistemic)))
            if u > self.student_escalate_threshold:
                self.store.grow(sess.sid, self.n_samples)
                self._last_escalated[sess.sid] = 1
                n += 1
        return n

    def _take_dropped(self) -> int:
        """Drops accumulated since the last metrics record (and reset)."""
        n, self._dropped_unreported = self._dropped_unreported, 0
        return n

    def _slot_count(self, n_sessions: int) -> int:
        """Session slots a tick launches with — the batch-layout contract.

        Fixed-shape modes pad idle slots to ``max_sessions`` so one
        compiled graph per shape serves every tick (dummy rows freeze
        after step 0, dropped); shard-aware placement then rounds up to a
        whole number of sessions per shard, so a session's S chains never
        straddle a device boundary and every shard launches the same
        shape.  Mask rows stay global — placement is a batch-layout
        concern only.  Single source for both :meth:`step` and
        :func:`repro.serve.scheduler.prewarm`: the prewarm guarantee is
        exactly "compiles the graph this formula will launch".
        """
        slots = self.max_sessions if self._fixed else n_sessions
        return -(-slots // self._shards) * self._shards

    def _apply(self, x_batch, rows, lengths, initial_state):
        """One batched model launch — the tick hot path.

        Factored out of :meth:`step` so :func:`repro.serve.scheduler.prewarm`
        can drive the *exact* serving graph (same shapes, dtypes and state
        pytree) at boot, compiling every ladder rung before traffic arrives.
        Returns ``(model outputs tuple, per-layer states)`` — for the
        autoencoder the outputs are ``(mean, log_var, dec_out)``: the
        decoder hidden sequence is requested unconditionally (``_ae.apply``
        is not itself jitted, so the extra return changes no numerics and
        keeps the graph independent of whether any student row is present;
        the student summary path reads it).
        """
        if self.kind == "classifier":
            logits, states = _clf.apply(
                self.params, x_batch, rows, self.cfg, backend=self.backend,
                initial_state=initial_state, lengths=lengths,
                return_state=True, mesh=self.mesh, policy=self.policy,
                precision=self.precision)
            return (logits,), states
        mean, log_var, dec_out, states = _ae.apply(
            self.params, x_batch, rows, self.cfg, backend=self.backend,
            initial_state=initial_state, lengths=lengths,
            return_state=True, return_decoded=True, mesh=self.mesh,
            policy=self.policy, precision=self.precision)
        return (mean, log_var, dec_out), states

    def _gather_states(self, sessions, dtype, n_pad: int = 0):
        """Concatenate per-session carries into batch-aligned layer states.

        Fresh sessions (and fixed-shape pad slots) contribute zeros in the
        backend's own carry dtypes (h in the activation dtype; LSTM c in
        fp32 on the Pallas backends, the activation dtype on reference), so
        a mixed fresh/resumed batch is bit-identical to serving each session
        alone.  The per-layer pytree follows the cell: ``(h, c)`` for LSTM,
        ``(h,)`` for GRU — whatever ``run_stack`` returned is what a session
        stored, part by part.  In fixed-shape mode zeros are always
        materialized: an all-fresh first tick must present the same jit
        pytree as every later tick, or the one-graph guarantee would break
        on tick two.

        Which sessions are fresh is read here, on the host; the
        concatenation is one compiled call over every layer and part
        (:func:`_concat_carries`).  A fresh session passes cached zeros of
        its resumed shape, so freshness never enters the compiled layout.
        """
        if all(sess.fresh for sess in sessions) and not self._fixed:
            return None
        if self.precision is not None:
            # Serving precision fixes the carry dtypes on every backend:
            # h in the activation dtype, LSTM c in fp32 (run_stack's 32-bit
            # cell-state policy).  prewarm passes the host chunk dtype, so
            # the mapping lives here, not in step().
            dtype = _quant.activation_dtype(self.precision, dtype)
            c_dtype = jnp.float32
        else:
            c_dtype = dtype if self.backend == "reference" else jnp.float32
        part_dtypes = tuple(np.dtype(dt) for dt in (
            (dtype,) if self.cell == "gru" else (dtype, c_dtype)))
        part_specs = tuple(tuple((hid, dt) for dt in part_dtypes)
                           for hid in self._encoder_hiddens())
        per_session = []
        for sess in sessions:
            if sess.fresh:
                # Zeros sized by the session's *own* chain count — the
                # batch layout packs per-session S, not the ceiling.
                rows = int(sess.rows.shape[0])
                per_session.append(tuple(
                    tuple(self._zero_carry(rows, hid, dt) for hid, dt in specs)
                    for specs in part_specs))
            else:
                per_session.append(tuple(tuple(layer)
                                         for layer in sess.state))
        return self._carry_call(_concat_carries, tuple(per_session),
                                int(n_pad), part_specs)

    def _zero_carry(self, rows: int, hidden: int, dtype):
        key = (rows, hidden, dtype)
        if key not in self._zero_carries:
            self._zero_carries[key] = jnp.zeros((rows, hidden), dtype)
        return self._zero_carries[key]

    def _carry_call(self, fn, arrays, *static):
        """Call a carry-path program; count a layout this engine has not
        run before into the tick's ``carry_layouts_new``.

        A layout is the static arguments and each array's shape and dtype:
        what the compiled program is keyed by.
        """
        key = (fn.__name__, static, tuple(
            (a.shape, a.dtype) for a in jax.tree.leaves(arrays)))
        if key not in self._carry_layouts:
            self._carry_layouts.add(key)
            self._carry_layouts_new += 1
        return fn(arrays, *static)

    def _encoder_hiddens(self):
        if self.kind == "classifier":
            return (self.cfg.hidden,) * self.cfg.num_layers
        return self.cfg.encoder_hiddens
