"""Multi-tenant fleet engine: heterogeneous Bayesian RNN workloads, one tick.

A real monitoring fleet is not one model: an ICU ward mixes LSTM ECG
classifiers, GRU anomaly autoencoders, cheap low-priority int8 tenants —
different cells, widths, MC sample counts and precisions, each under its own
SLO.  The :class:`~repro.serve.stream.StreamingEngine` serves exactly one
``(cell, task, H, S, precision)`` config per instance; this module is the
layer above, where the serving stack becomes a *service*:

* **Tenants** (:class:`TenantSpec`) declare a model config + params, a
  priority weight and capacity.  Tenants whose sessions would compile the
  same graph family — same params object and same ``(config, backend,
  precision, chunk policy)`` — fold into one **launch group**: a single
  shared ``StreamingEngine`` whose tick batches every submitting session of
  every member tenant into one ``pallas_seq`` launch per layer (the paper's
  sample-wise pipelining, generalized session-wise in PR 2, now
  tenant-wise).  Heterogeneous tenants get their own groups; a fleet tick
  is one engine tick per active group.
* **Weighted-fair admission**: all tenants share one bounded
  :class:`~repro.serve.admission.WeightedFairQueue`.  Under overload the
  admitted-capacity shares converge to the tenant weights, order within a
  tenant is FIFO, and an aging guard keeps any starved low-weight tenant
  admitting eventually.
* **Per-tenant observability**: every fleet tick emits one tenant-tagged
  :class:`~repro.serve.scheduler.TickMetrics` per involved tenant
  (``tenant=`` field) into the fleet's sink; ``scheduler.summarize`` groups
  them, so each tenant's p95/queue-wait/drop counts read off its own slice.
* **One atomic snapshot**: :meth:`FleetEngine.snapshot` commits every
  group's sessions, the shared queue and the fairness ledger under a single
  sha256 manifest (``repro.serve.persistence.snapshot_fleet``); kill →
  :meth:`restore` resumes every tenant bit-identically.

Bit-exactness carries over wholesale: the per-group engines are unmodified
``StreamingEngine`` instances, and batch composition / launch shape / chunk
split invariance (PR 2/PR 6) is exactly why a tenant served inside a shared
fleet tick is bit-identical to the same tenant alone in its own
single-tenant engine from the same carried state — the heterogeneity pin in
``tests/test_fleet.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import numpy as np

from repro.core import autoencoder as _ae, classifier as _clf
from repro.serve import persistence as _persist
from repro.serve.admission import (DrainRejected, FleetTicket,
                                   WeightedFairQueue)
from repro.serve.scheduler import TickMetrics
from repro.serve.sessions import CapacityError, Session
from repro.serve.stream import (ChunkResult, MetricsSink, RingBufferSink,
                                StreamingEngine)


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant of the fleet: a model, its capacity and its priority.

    ``cfg`` fixes the architecture, cell and MC-dropout block (S rides in
    ``cfg.mcd.n_samples``; ``n_samples`` here overrides it without the
    caller rebuilding the config).  ``weight`` is the tenant's share of
    admitted capacity under overload — twice the weight, twice the admitted
    sessions per unit time once every tenant is backlogged.  ``slo`` is
    opaque to the engine (the fleet controller reads it); ``max_sessions``
    is the tenant's own live-session cap, enforced even inside a shared
    launch group.
    """

    name: str
    cfg: Any                       # ClassifierConfig | AutoencoderConfig
    params: Any
    weight: float = 1.0
    n_samples: int | None = None   # override cfg.mcd.n_samples — the
                                   # tenant's chain *ceiling*: sessions
                                   # admit at it and early exit retires
                                   # below it, never above
    precision: str | None = None
    backend: str = "pallas_seq"
    max_sessions: int = 64
    chunk_capacity: int | str | None = None
    slo: Any = None                # SLOPolicy, read by FleetController
    early_exit_threshold: float | None = None  # staged early-exit sampling
                                   # (StreamingEngine docstring); part of
                                   # the launch-group signature — tenants
                                   # sharing an engine share the policy
    min_samples: int = 1           # early-exit floor for this tenant
    student: Any = None            # distilled student heads enabling
                                   # mode="student" admissions for this
                                   # tenant (repro.core.distill); identity
                                   # is part of the launch-group signature
                                   # like params
    student_escalate_threshold: float | None = None  # MC fallback trigger
                                   # (StreamingEngine docstring); group
                                   # signature too — co-batched tenants
                                   # share the escalation policy

    def __post_init__(self):
        if "/" in self.name:
            raise ValueError(f"tenant name {self.name!r} may not contain "
                             "'/' (reserved for fleet sid namespacing)")
        if not self.weight > 0:
            raise ValueError(f"tenant {self.name!r} weight must be > 0, "
                             f"got {self.weight}")
        if not isinstance(self.cfg, (_clf.ClassifierConfig,
                                     _ae.AutoencoderConfig)):
            raise TypeError(f"tenant {self.name!r}: unsupported config "
                            f"type {type(self.cfg).__name__}")

    def resolved_cfg(self):
        """The model config with the S override folded in."""
        if (self.n_samples is None
                or self.n_samples == self.cfg.mcd.n_samples):
            return self.cfg
        return dataclasses.replace(
            self.cfg, mcd=self.cfg.mcd.replace(n_samples=self.n_samples))


@dataclasses.dataclass
class _Group:
    """One launch group: a shared engine + the tenants folded into it."""

    name: str
    engine: StreamingEngine
    tenants: list[str]


class FleetEngine:
    """Serve a set of heterogeneous tenants, one weighted-fair tick at a time.

    Args:
      tenants: the fleet's :class:`TenantSpec` table (names unique).
      max_pending: bound of the shared admission queue (fleet-wide).
      aging_rounds: drain rounds after which a starved head-of-line ticket
        bypasses the weighted-fair pick (see ``WeightedFairQueue``).
      metrics_sink: where tenant-tagged per-tick :class:`TickMetrics` go
        (fleet-level; each group engine keeps a small private ring for its
        own launch-shape bookkeeping).
      mesh, policy: forwarded to every group engine.

    Session ids are namespaced ``"tenant/sid"`` inside the launch groups so
    tenants sharing a group can never collide; the public API (``admit``,
    ``step``, ``close``) speaks (tenant, bare-sid) pairs throughout.
    """

    def __init__(self, tenants: Sequence[TenantSpec], *,
                 max_pending: int = 256, aging_rounds: int = 16,
                 admit_per_tick: int | None = None,
                 metrics_window: int = 4096,
                 metrics_sink: MetricsSink | None = None,
                 mesh=None, policy=None):
        if not tenants:
            raise ValueError("a fleet needs at least one tenant")
        names = [t.name for t in tenants]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate tenant names in {names}")
        self.specs: dict[str, TenantSpec] = {t.name: t for t in tenants}
        self._mesh, self._policy = mesh, policy
        # Launch-group folding: tenants sharing the same weights *object*
        # and the same compiled signature (config incl. cell/H/NL/mcd,
        # backend, precision, chunk policy, early-exit policy) share one
        # engine — their sessions batch into the same per-layer launches.
        # S is *not* part of the signature (unsharded): per-session chain
        # counts made it session state, so a 4-chain tenant co-batches
        # with an 8-chain tenant under the group ceiling (max member S).
        # A meshed fleet keeps S in the signature — sharded launches place
        # whole sessions per shard assuming one S.  Different params can
        # never share a launch, so they never share a group.
        self.groups: dict[str, _Group] = {}
        self._tenant_group: dict[str, str] = {}
        self._group_seq = 0      # names must never recycle: a reconfigured
        #                          tenant's fresh group could otherwise be
        #                          named after — and then deleted with — the
        #                          emptied group it replaces
        by_sig: dict[tuple, list[TenantSpec]] = {}
        for spec in tenants:
            cfg = spec.resolved_cfg()
            cfg_key = cfg if mesh is not None else dataclasses.replace(
                cfg, mcd=cfg.mcd.replace(n_samples=1))
            sig = (id(spec.params), cfg_key, spec.backend,
                   spec.precision, spec.chunk_capacity,
                   spec.early_exit_threshold, spec.min_samples,
                   id(spec.student) if spec.student is not None else None,
                   spec.student_escalate_threshold)
            by_sig.setdefault(sig, []).append(spec)
        for members in by_sig.values():
            self._make_group([m.name for m in members])
        self.queue = WeightedFairQueue(
            {t.name: t.weight for t in tenants},
            max_pending=max_pending, aging_rounds=aging_rounds)
        # The shared admission budget the weights ration.  When set, the
        # fleet is rate-limited: admit() only queues, and each step() drains
        # at most this many admissions split weighted-fair across backlogged
        # tenants.  None: admissions drain eagerly on submit/close — each
        # tenant then fills its own free rows and fair shares only bind
        # inside a shared launch group's store.
        self.admit_per_tick = admit_per_tick
        self.metrics_sink: MetricsSink = (metrics_sink
                                          or RingBufferSink(metrics_window))
        self.tick = 0
        self.dropped_admissions: list = []
        self._dropped_unreported: dict[str, int] = {n: 0 for n in names}

    def _resolved_s(self, tenant: str) -> int:
        """The tenant's chain ceiling (spec S override folded in)."""
        cfg = self.specs[tenant].resolved_cfg()
        return max(1, cfg.mcd.n_samples if cfg.mcd.any_bayesian else 1)

    def _make_group(self, members: list[str],
                    engine: StreamingEngine | None = None) -> _Group:
        """Register a launch group for ``members`` (build its engine).

        The group engine's chain ceiling is the max member S — members
        with a smaller S admit their sessions below it (per-session chain
        counts), and the engine's launch shapes are sized by the ceiling.
        """
        gname = f"g{self._group_seq}"
        self._group_seq += 1
        if engine is None:
            lead = self.specs[members[0]]
            ceiling = max(self._resolved_s(m) for m in members)
            cfg = lead.resolved_cfg()
            if cfg.mcd.any_bayesian and cfg.mcd.n_samples != ceiling:
                cfg = dataclasses.replace(
                    cfg, mcd=cfg.mcd.replace(n_samples=ceiling))
            engine = StreamingEngine(
                lead.params, cfg, backend=lead.backend,
                max_sessions=sum(self.specs[m].max_sessions
                                 for m in members),
                chunk_capacity=lead.chunk_capacity,
                metrics_sink=RingBufferSink(64),
                mesh=self._mesh, policy=self._policy,
                precision=lead.precision,
                early_exit_threshold=lead.early_exit_threshold,
                min_samples=min(lead.min_samples, ceiling),
                student=lead.student,
                student_escalate_threshold=lead.student_escalate_threshold)
        group = _Group(name=gname, engine=engine, tenants=list(members))
        self.groups[gname] = group
        for m in members:
            self._tenant_group[m] = gname
        return group

    # -- addressing ----------------------------------------------------------
    def group_of(self, tenant: str) -> _Group:
        try:
            return self.groups[self._tenant_group[tenant]]
        except KeyError:
            raise KeyError(f"unknown tenant {tenant!r} (fleet serves "
                           f"{sorted(self.specs)})") from None

    @staticmethod
    def _gsid(tenant: str, sid: str) -> str:
        return f"{tenant}/{sid}"

    def _live_count(self, tenant: str) -> int:
        store = self.group_of(tenant).engine.store
        prefix = tenant + "/"
        return sum(1 for sid in store.active if sid.startswith(prefix))

    def _has_room(self, tenant: str) -> bool:
        """Per-tenant admission eligibility (the drain's ``has_room``)."""
        return (self._live_count(tenant)
                < self.specs[tenant].max_sessions)

    # -- session lifecycle ---------------------------------------------------
    def admit(self, tenant: str, sid: str, *, priority: int = 0,
              session: Session | None = None,
              mode: str | None = None) -> Session | None:
        """Queue a stream for a tenant (and, unless rate-limited, drain).

        Mirrors ``StreamingEngine.admit``: returns the live
        :class:`Session` if the stream went live in this drain, None if it
        is queued (``QueueFull`` beyond ``max_pending``).  With
        ``admit_per_tick`` set the fleet is rate-limited: submissions only
        queue here and the budgeted weighted-fair drain runs at the next
        tick boundary.  ``session`` makes it a re-attach (an evicted carry
        resumes the same draw; its sid is re-namespaced into the tenant's
        group).  ``mode="student"`` queues a distilled fast-path admission
        (the tenant's spec must carry ``student`` heads).
        """
        engine = self.group_of(tenant).engine
        gsid = self._gsid(tenant, sid)
        if gsid in engine.store:
            raise ValueError(f"session {sid!r} already admitted "
                             f"for tenant {tenant!r}")
        if mode == "student" or (session is not None
                                 and session.mode == "student"):
            engine._check_student(gsid)
        if session is not None:
            # Same eager checks as StreamingEngine.admit — fail the caller
            # now, not whichever tick happens to drain the ticket.
            if session.seed != engine.store.seed:
                raise ValueError(
                    f"session {sid!r} was drawn under seed "
                    f"{session.seed!r}, tenant {tenant!r} uses "
                    f"{engine.store.seed!r}")
            if int(session.rows.shape[0]) > self._resolved_s(tenant):
                raise ValueError(
                    f"session {sid!r} carries "
                    f"{int(session.rows.shape[0])} MC chains, tenant "
                    f"{tenant!r}'s ceiling is {self._resolved_s(tenant)}")
            if session.sid != gsid:
                session = dataclasses.replace(session, sid=gsid)
        self.queue.submit(tenant, gsid, priority=priority, session=session,
                          mode=mode)
        if self.admit_per_tick is not None:
            # Rate-limited mode: admissions happen only at tick boundaries,
            # where the budget is split weighted-fair — an immediate drain
            # here would let submit order bypass the rationing.
            return None
        try:
            self.queue.drain(self._admit_ticket, self._has_room)
        except DrainRejected as err:
            # The caller is synchronously present for *its own* ticket: a
            # reject of this submit must raise, not read as "queued".
            # Other tickets' poison is contained (recorded per tenant).
            mine = next((e for t, e in err.rejected if t.sid == gsid), None)
            others = [(t, e) for t, e in err.rejected if t.sid != gsid]
            self._record_drops(others)
            if mine is not None:
                raise mine from err
        store = engine.store
        return store.get(gsid) if gsid in store else None

    def close(self, tenant: str, sid: str) -> Session:
        """Evict a tenant's stream; the freed row feeds the shared queue.

        Returns the final :class:`Session` with its bare (un-namespaced)
        sid, ready to re-``admit`` later.
        """
        sess = self.group_of(tenant).engine.store.evict(
            self._gsid(tenant, sid))
        if self.admit_per_tick is None:
            self._drain()
        return dataclasses.replace(sess, sid=sid)

    def _admit_ticket(self, ticket: FleetTicket) -> Session:
        """Route one drained ticket into its tenant's launch group.

        Fresh sessions open at the *tenant's* ceiling, which may sit below
        the group engine's (the group ceiling is the max member S); student
        tickets open one deterministic row instead.
        """
        store = self.group_of(ticket.tenant).engine.store
        if ticket.session is not None:
            return store.attach(ticket.session)
        if ticket.mode == "student":
            return store.admit(ticket.sid, mode="student")
        return store.admit(ticket.sid,
                           n_samples=self._resolved_s(ticket.tenant))

    def _record_drops(self, rejected: list) -> None:
        self.dropped_admissions.extend(rejected)
        del self.dropped_admissions[:-1024]
        for ticket, _ in rejected:
            self._dropped_unreported[ticket.tenant] += 1

    def _drain(self) -> list[FleetTicket]:
        """One weighted-fair drain over every tenant's FIFO.

        Rejections are contained exactly like ``StreamingEngine._drain``:
        the poison ticket's drop is recorded (per-tenant, for the metrics
        trail) and serving continues.
        """
        try:
            return self.queue.drain(self._admit_ticket, self._has_room,
                                    self.admit_per_tick)
        except DrainRejected as err:
            self._record_drops(err.rejected)
            return err.admitted

    def sessions_of(self, tenant: str) -> list[Session]:
        """A tenant's live sessions (namespaced sids), admission order."""
        prefix = tenant + "/"
        return [s for s in self.group_of(tenant).engine.store.sessions()
                if s.sid.startswith(prefix)]

    @property
    def active_sessions(self) -> dict[str, list[str]]:
        """tenant → live bare sids."""
        out: dict[str, list[str]] = {}
        for name in self.specs:
            prefix = name + "/"
            out[name] = [s.sid[len(prefix):] for s in self.sessions_of(name)]
        return out

    @property
    def metrics(self) -> Sequence[TickMetrics]:
        return self.metrics_sink.window()

    def summarize(self) -> dict:
        from repro.serve.scheduler import summarize
        return summarize(list(self.metrics))

    # -- serving -------------------------------------------------------------
    def step(self, chunks: Mapping[str, Mapping[str, Any]]
             ) -> dict[str, dict[str, ChunkResult]]:
        """One fleet tick: drain the shared queue, launch every active group.

        ``chunks`` maps tenant → {bare sid → [t, input_dim] chunk}.  Every
        listed session must be live.  Each launch group with submissions
        runs one batched engine tick (sessions of all member tenants fold
        into the same per-layer launches); per-tenant tagged
        :class:`TickMetrics` land in the fleet sink — including a quiet
        record for tenants with queued-but-unserved work, so a starving
        tenant is visible in the trail it isn't serving in.  Returns
        tenant → {bare sid → :class:`ChunkResult`}.
        """
        self._drain()
        # Per-tenant queue wait measured after the drain — the head-of-line
        # age of the streams that still couldn't get a row.
        waits = {name: self.queue.oldest_wait_s(name) for name in self.specs}
        by_group: dict[str, dict[str, Any]] = {}
        tenant_lens: dict[str, dict[str, int]] = {}
        for tenant, tchunks in chunks.items():
            group = self.group_of(tenant)          # raises on unknown tenant
            if not tchunks:
                continue
            gmap = by_group.setdefault(group.name, {})
            lens = tenant_lens.setdefault(tenant, {})
            for sid, chunk in tchunks.items():
                x = np.asarray(chunk)
                gsid = self._gsid(tenant, sid)
                lens[gsid] = x.shape[0] if x.ndim else 1
                gmap[gsid] = chunk

        results: dict[str, dict[str, ChunkResult]] = {
            t: {} for t in chunks if chunks[t]}
        group_metrics: dict[str, TickMetrics] = {}
        for gname, gmap in by_group.items():
            engine = self.groups[gname].engine
            res = engine.step(gmap)
            gm = engine.last_metrics
            if gm is not None:
                group_metrics[gname] = gm
            for gsid, cr in res.items():
                tenant, sid = gsid.split("/", 1)
                results[tenant][sid] = dataclasses.replace(cr, sid=sid)

        # One tagged record per tenant that served, plus a quiet record for
        # tenants with pending or dropped work that got nothing this tick.
        # Chain accounting is per-session (the engine's _last_served_chains /
        # _last_reclaimed tick attribution): with early exit live, a
        # tenant's rows/chain-steps reflect its sessions' *own* chain
        # counts, not the group ceiling.
        for tenant, lens in tenant_lens.items():
            engine = self.group_of(tenant).engine
            gm = group_metrics.get(self._tenant_group[tenant])
            if gm is None:
                continue
            served = engine._last_served_chains
            chains = sum(served.get(gsid, 0) for gsid in lens)
            chain_steps = sum(L * served.get(gsid, 0)
                              for gsid, L in lens.items())
            reclaimed = sum(n for gsid, n in engine._last_reclaimed.items()
                            if gsid in lens)
            stu_rows = sum(n for gsid, n in
                           engine._last_student_rows.items() if gsid in lens)
            escal = sum(n for gsid, n in engine._last_escalated.items()
                        if gsid in lens)
            live = int(sum(lens.values()))
            self.metrics_sink.emit(dataclasses.replace(
                gm, tick=self.tick, tenant=tenant,
                n_chunks=len(lens), live_rows=chains,
                live_steps=live, live_chain_steps=chain_steps,
                tokens_per_sec=(chain_steps / gm.duration_s
                                if gm.duration_s > 0 else 0.0),
                queue_depth=self.queue.depth_of(tenant),
                queue_wait_s=waits[tenant],
                dropped=self._take_dropped(tenant),
                active_chains=self._active_chains(tenant),
                reclaimed_rows=reclaimed,
                student_rows=stu_rows, escalations=escal))
        for tenant in self.specs:
            if tenant in tenant_lens:
                continue
            dropped = self._take_dropped(tenant)
            if not (dropped or self.queue.depth_of(tenant)):
                continue
            self.metrics_sink.emit(TickMetrics(
                tick=self.tick, capacity=0, n_chunks=0, live_rows=0,
                batch_rows=0, queue_depth=self.queue.depth_of(tenant),
                live_steps=0, live_chain_steps=0, padded_steps=0,
                pad_waste=0.0, duration_s=0.0, tokens_per_sec=0.0,
                queue_wait_s=waits[tenant], dropped=dropped,
                active_chains=self._active_chains(tenant),
                tenant=tenant))
        self.tick += 1
        return results

    def _active_chains(self, tenant: str) -> int:
        """Live MC chains across one tenant's sessions (post-retire gauge)."""
        return sum(int(s.rows.shape[0]) for s in self.sessions_of(tenant))

    def _take_dropped(self, tenant: str) -> int:
        n, self._dropped_unreported[tenant] = \
            self._dropped_unreported[tenant], 0
        return n

    # -- reconfiguration (the fleet controller's apply path) -----------------
    def reconfigure_tenant(self, tenant: str, new) -> StreamingEngine:
        """Swap one tenant to a new serving config, sessions intact.

        ``new`` is a ``repro.serve.controller.ServingConfig`` (duck-typed:
        ``n_samples``/``precision``/``chunk_capacity`` attributes).  The
        tenant's sessions are converted (``convert_session`` — a downshift
        keeps the first S′ chains bit-exactly, an upshift appends fresh
        rows) and moved into a dedicated new launch group; other tenants
        sharing the old group are untouched.  Both stores' row allocators
        advance past every row the transfer drew, so no later admission in
        either group can repeat a Bayesian draw.
        """
        # Deferred: the controller layer imports repro.dse; the data plane
        # must not pay that import unless a reconfig actually happens.
        from repro.serve.controller import carry_dtypes, convert_session

        spec = self.specs[tenant]
        old_ceiling = self._resolved_s(tenant)
        old_group = self.group_of(tenant)
        old_engine = old_group.engine
        new_cap = getattr(new, "chunk_capacity", 0) or spec.chunk_capacity
        new_spec = dataclasses.replace(
            spec, n_samples=int(new.n_samples),
            precision=getattr(new, "precision", spec.precision),
            chunk_capacity=new_cap)
        self.specs[tenant] = new_spec

        moved = self.sessions_of(tenant)
        for sess in moved:
            old_engine.store.evict(sess.sid)
        old_group.tenants.remove(tenant)

        # Always a dedicated fresh group: an existing group's store
        # allocated rows independently, so folding a reconfigured tenant
        # into it could only collide.  The new store's cursor starts past
        # everything the old group ever drew (same seed space).
        new_ceiling = max(1, int(new.n_samples))
        engine = StreamingEngine(
            new_spec.params, new_spec.resolved_cfg(),
            backend=new_spec.backend, max_sessions=new_spec.max_sessions,
            chunk_capacity=new_spec.chunk_capacity,
            metrics_sink=RingBufferSink(64),
            mesh=self._mesh, policy=self._policy,
            precision=new_spec.precision,
            early_exit_threshold=new_spec.early_exit_threshold,
            min_samples=min(new_spec.min_samples, new_ceiling))
        cursor = old_engine.store.next_row
        part_dtypes = carry_dtypes(engine.cell, new_spec.precision,
                                   engine.backend)
        for sess in moved:
            extra = None
            s_i = int(np.asarray(sess.rows).shape[0])
            # A session at the old tenant ceiling follows the new ceiling;
            # one early exit already shrank keeps its earned smaller S
            # (capped) — the swap must not resurrect retired chains.
            target = (engine.n_samples if s_i == old_ceiling
                      else min(s_i, engine.n_samples))
            missing = target - s_i
            if missing > 0:
                extra = np.arange(cursor, cursor + missing, dtype=np.uint32)
                cursor += missing
            engine.store.attach(convert_session(
                sess, n_samples=target, part_dtypes=part_dtypes,
                extra_rows=extra))
        engine.store._next_row = max(engine.store.next_row, cursor)
        old_engine.store._next_row = max(old_engine.store.next_row, cursor)
        engine.tick = old_engine.tick
        group = self._make_group([tenant], engine=engine)
        if not old_group.tenants:
            del self.groups[old_group.name]
        return group.engine

    # -- durability ----------------------------------------------------------
    def snapshot(self, directory: str, *, step: int | None = None) -> str:
        """One atomic manifest covering every tenant: kill → restore bit-id.

        Per group: every live session's carry + the engine meta (tick,
        cell, precision, mcd — the same dict a standalone engine snapshot
        validates).  Fleet-wide: the tenant table (name → group, weight,
        S, precision), the shared queue's tickets (attached carries
        included) and the fairness ledger.  All of it commits in one
        ``os.replace``.
        """
        groups = {g.name: (g.engine.store, g.engine._engine_meta())
                  for g in self.groups.values()}
        tenants = {
            name: {"group": self._tenant_group[name],
                   "weight": self.specs[name].weight,
                   # The tenant's own ceiling (may sit below its group
                   # engine's — the group ceiling is the max member S).
                   "n_samples": self._resolved_s(name),
                   "precision": self.specs[name].precision,
                   "backend": self.specs[name].backend}
            for name in self.specs}
        return _persist.snapshot_fleet(
            directory, groups=groups, tenants=tenants,
            queue=self.queue.waiting(), fair=self.queue.state(),
            tick=self.tick, step=step)

    def restore(self, directory: str, *, step: int | None = None) -> dict:
        """Resume a whole fleet from one manifest (fresh fleet only).

        Accepts two layouts: a fleet snapshot (every tenant, the shared
        queue and the fairness ledger restore together), or — for a
        single-tenant fleet — a plain pre-fleet ``StreamingEngine``
        snapshot, whose sessions are adopted under the tenant's namespace
        (the typed mismatch errors of ``StreamingEngine.restore`` apply
        unchanged).  Returns the fleet meta dict.
        """
        for g in self.groups.values():
            if g.engine.store.sessions() or len(self.queue):
                raise RuntimeError("restore() needs a fresh fleet: live or "
                                   "queued sessions would collide")
        peek = _persist.load_any_snapshot_meta(directory, step)
        if "sessions" in peek:          # legacy single-engine layout
            return self._restore_single(directory, step=peek["step"])
        meta, stores = _persist.restore_fleet(directory, step=peek["step"])
        snap_tenants = meta["tenants"]
        if set(snap_tenants) != set(self.specs):
            raise ValueError(
                f"fleet snapshot serves tenants "
                f"{sorted(snap_tenants)}, this fleet serves "
                f"{sorted(self.specs)}")
        # Tenant → group assignment must agree structurally: the snapshot's
        # grouping was derived from the same folding rule, so mismatched
        # membership means mismatched specs.
        for name, t_meta in snap_tenants.items():
            mine = sorted(self.group_of(name).tenants)
            theirs = sorted(n for n, m in snap_tenants.items()
                            if m["group"] == t_meta["group"])
            if mine != theirs:
                raise ValueError(
                    f"tenant {name!r} shares a launch group with {theirs} "
                    f"in the snapshot but {mine} in this fleet — the specs "
                    "diverge")
        # Validate + adopt per snapshot group, through the standalone
        # engine's own typed checks (n_samples, seed, cell, precision, mcd).
        for gname_s, (store, g_meta) in stores.items():
            members = [n for n, m in snap_tenants.items()
                       if m["group"] == gname_s]
            group = self.group_of(members[0])
            engine_meta = group.engine._check_restore_meta(g_meta)
            store.max_sessions = group.engine.max_sessions
            group.engine._adopt(store, group.engine.queue, engine_meta)
        self.queue.load_state(meta.get("fair") or {})
        for entry in meta["queue"]:
            self.queue.submit(entry["tenant"], entry["sid"],
                              priority=entry["priority"],
                              session=entry.get("session_obj"),
                              mode=entry.get("mode"))
        self.tick = int(meta.get("tick", 0))
        return meta

    def _restore_single(self, directory: str, *, step: int) -> dict:
        """Adopt a pre-fleet single-engine snapshot as a one-tenant fleet."""
        if len(self.specs) != 1:
            raise ValueError(
                f"snapshot is a single-engine layout; this fleet serves "
                f"{len(self.specs)} tenants ({sorted(self.specs)}) — only "
                "a one-tenant fleet can adopt it")
        (tenant,) = self.specs
        engine = self.group_of(tenant).engine
        extra = engine.restore(directory, step=step)
        # Namespace the adopted sessions and wait-list under the tenant.
        prefix = tenant + "/"
        for sess in list(engine.store.sessions()):
            if sess.sid.startswith(prefix):
                continue
            engine.store.evict(sess.sid)
            engine.store.attach(dataclasses.replace(
                sess, sid=self._gsid(tenant, sess.sid)))
        for ticket in engine.queue.waiting():
            engine.queue.cancel(ticket.sid)
            sess = ticket.session
            if sess is not None and not sess.sid.startswith(prefix):
                sess = dataclasses.replace(
                    sess, sid=self._gsid(tenant, sess.sid))
            self.queue.submit(tenant, self._gsid(tenant, ticket.sid),
                              priority=ticket.priority, session=sess,
                              mode=ticket.mode)
        self.tick = engine.tick
        return {"tenants": {tenant: {"group": self._tenant_group[tenant]}},
                "tick": self.tick, "extra": extra}
