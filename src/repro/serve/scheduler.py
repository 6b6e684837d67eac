"""Adaptive tick scheduling: pick the launch shape from the observed load.

The streaming engine has two shape policies from PR 2: dynamic (pad each
tick to its own max chunk length — minimal FLOPs, but every new
``(T, batch)`` pair retraces and recompiles) and fixed (hand-set
``chunk_capacity`` — one compiled graph forever, but the operator has to
guess the right capacity up front and eats the pad waste of a bad guess).

This scheduler closes the loop: it watches the ragged chunk-length
distribution and, per tick, picks a capacity from a small **ladder** of
pre-warmable fixed shapes.  Compilation stays bounded by the ladder length
(each rung is one graph, exactly like PR 2's fixed-shape mode), while the
rung tracks the observed load — a quiet night of short chunks slides down
to a small rung, a burst of long chunks climbs, and the mask/carry numerics
never notice because the lengths-pinned graph family is bit-identical
across launch shapes (docs/kernels.md).

Per tick it also emits :class:`TickMetrics` — rows occupied, queue depth,
pad waste, tokens/sec — the control-plane observables the ROADMAP's
"serve heavy traffic" north star needs before any autoscaling can exist.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Iterable, Sequence


def pow2_ladder(max_capacity: int, *, first: int = 8) -> tuple[int, ...]:
    """Power-of-two rungs up to a top rung of exactly ``max_capacity``.

    Every rung honors ``max_capacity`` — the ladder is the operator's stated
    launch-shape budget, and the scheduler rejects chunks above its top rung,
    so a rung above the cap would silently accept chunks longer than the
    operator allowed (that was a real bug: ``pow2_ladder(4)`` used to return
    ``(8,)``, and ``pow2_ladder(100)`` topped out at 128).
    """
    if max_capacity < 1:
        raise ValueError(f"max_capacity must be >= 1, got {max_capacity}")
    rungs, c = [], min(max(1, first), max_capacity)
    while c < max_capacity:
        rungs.append(c)
        c *= 2
    rungs.append(max_capacity)
    return tuple(rungs)


@dataclasses.dataclass
class TickMetrics:
    """Per-tick control-plane observables (host-side, no device sync).

    Times are host time: on an accelerator the device work a tick
    dispatched may still be running when ``step`` returns.
    """

    tick: int
    capacity: int          # launch T this tick (ladder rung / fixed / max len)
    n_chunks: int          # sessions served this tick
    live_rows: int         # session-chain rows carrying real data
    batch_rows: int        # launch rows incl. idle-slot padding
    queue_depth: int       # admissions still waiting after the drain
    live_steps: int        # sum of chunk lengths (signal timesteps served)
    live_chain_steps: int  # live_steps x S MC chains (chain-timesteps)
    padded_steps: int      # batch_rows * capacity (chain-timesteps launched)
    pad_waste: float       # 1 - live_chain_steps/padded_steps
    duration_s: float      # host time of StreamingEngine.step (drain to
                           # return), not device time: the launch it
                           # dispatched may still run on an accelerator
    tokens_per_sec: float  # live chain-timesteps / duration_s (host rate)
    shards: int = 1        # data-parallel width the tick launched across
    queue_wait_s: float = 0.0  # oldest-pending admission age at the drain
    compiles: int = 0      # backend compiles during the tick, eager ops
                           # included (a slow tick with compiles > 0 is a
                           # compile stall, not overload — the co-design
                           # controller and any operator reading the JSONL
                           # trail need the split)
    phase_s: dict = dataclasses.field(default_factory=dict)
                           # host time of each phase of the tick, keyed by
                           # its span name (repro.serve.spans.PHASES); the
                           # values sum to at most duration_s
    gc_s: float = 0.0      # Python GC pauses that fell inside the tick
    dropped: int = 0       # admissions the store refused this tick (tickets
                           # drained out of the queue that could never go
                           # live — previously visible only in the engine's
                           # in-memory dropped_admissions deque)
    active_chains: int = 0     # live MC chains across the whole store at
                               # tick end (post-retire) — with early-exit
                               # sampling this drifts below sessions x S,
                               # and it is what expected-chain cost pricing
                               # (dse.calibrate) reads
    reclaimed_rows: int = 0    # chain rows retired by early exit this tick
                               # (freed batch capacity; row ids stay burned)
    student_rows: int = 0      # rows served on the distilled fast path this
                               # tick (one per student session — the rest of
                               # the batch is MC chains)
    escalations: int = 0       # student sessions that crossed the
                               # uncertainty threshold this tick and regrew
                               # to S fresh MC chains (store.grow)
    carry_layouts_new: int = 0  # carry gather/split layouts the engine ran
                                # for the first time this tick (each a
                                # compiled program); 0 once a fixed-shape
                                # stream has seen every tick size
    tenant: str | None = None  # owning tenant when the record came from a
                               # FleetEngine tick (None: single-tenant
                               # engine); summarize() groups on it


class AdaptiveTickScheduler:
    """Pick ``chunk_capacity`` online from the ragged-chunk distribution.

    Args:
      ladder: ascending candidate capacities; each rung is one compiled
        graph, so ``len(ladder)`` bounds total recompiles for life.
      window: how many recent chunk lengths inform the choice.
      percentile: the rung must cover this percentile of the window (100 =
        the windowed max).  Lower values shrink pad waste for long-tailed
        loads at the cost of climbing a rung when an outlier does arrive.
        The current tick's own max is always covered regardless.
    """

    def __init__(self, ladder: Sequence[int] | None = None, *,
                 max_capacity: int = 512, window: int = 64,
                 percentile: float = 100.0):
        self.ladder = tuple(sorted(ladder)) if ladder \
            else pow2_ladder(max_capacity)
        if not self.ladder or any(c < 1 for c in self.ladder):
            raise ValueError(f"bad capacity ladder {self.ladder}")
        if not 0.0 < percentile <= 100.0:
            raise ValueError(f"percentile must be in (0, 100], "
                             f"got {percentile}")
        self.percentile = float(percentile)
        self._window: deque[int] = deque(maxlen=int(window))

    @property
    def max_capacity(self) -> int:
        return self.ladder[-1]

    def plan(self, lens: Iterable[int]) -> int:
        """Record this tick's chunk lengths; return the capacity to launch.

        Chunks longer than the top rung are rejected exactly like PR 2's
        fixed-shape mode rejects over-capacity chunks — the ladder is the
        pre-warmed shape budget, not a suggestion.
        """
        lens = [int(n) for n in lens]
        if not lens:
            return self.ladder[0]
        need = max(lens)
        if need > self.ladder[-1]:
            raise ValueError(
                f"chunk of {need} steps exceeds the capacity ladder "
                f"(top rung {self.ladder[-1]}); split the chunk or extend "
                "the ladder")
        self._window.extend(lens)
        target = max(need, self._percentile_target())
        for rung in self.ladder:
            if rung >= target:
                return rung
        return self.ladder[-1]

    def _percentile_target(self) -> int:
        win = sorted(self._window)
        if not win:
            return self.ladder[0]
        k = max(0, min(len(win) - 1,
                       int(round(self.percentile / 100.0 * len(win))) - 1))
        return win[k]

    # -- persistence hooks (repro.serve.persistence) -------------------------
    def state(self) -> dict:
        """JSON-able state: the observation window."""
        return {"window": list(self._window)}

    def load_state(self, state: dict) -> None:
        self._window.extend(int(n) for n in state.get("window", ()))


def prewarm(engine, *, dtype=None) -> list[int]:
    """Compile every capacity rung at boot instead of on first use.

    PR 3's adaptive ladder bounds total recompiles by the ladder length,
    but each rung still compiled lazily on the first tick that needed it —
    a latency spike landing on whichever patient stream happened to trigger
    the climb.  This walks the engine's ladder (or its single fixed
    capacity) and drives the *exact* serving graph for each rung — same
    batch layout (``max_sessions`` slots padded to the shard multiple, S
    chains each), same dtypes, same materialized state pytree — so the
    first real tick of any shape hits a warm jit cache.  Dynamic-shape
    engines (``chunk_capacity=None``) have no finite shape family to warm
    and are rejected.

    Args:
      engine: a ``StreamingEngine`` with ``chunk_capacity`` an int or
        ``"auto"``.
      dtype: chunk dtype traffic will arrive in (default float32 — what
        the launchers feed; a mismatched dtype would compile a second
        graph family on the first real tick).

    Returns the list of capacities compiled, ascending.
    """
    import jax

    if engine._scheduler is not None:
        caps = list(engine._scheduler.ladder)
    elif isinstance(engine.chunk_capacity, int):
        caps = [engine.chunk_capacity]
    else:
        raise ValueError(
            "prewarm needs a bounded shape family: chunk_capacity must be "
            "an int or 'auto' (dynamic mode compiles per observed shape)")
    for cap in caps:
        jax.block_until_ready(engine._apply(*launch_args(engine, cap, dtype)))
    return caps


def launch_args(engine, capacity: int, dtype=None) -> tuple:
    """Operands of a fixed-shape tick launch at ``capacity`` timesteps.

    ``(x, rows, lengths, state)`` for ``engine._apply`` in the exact
    layout :meth:`~repro.serve.stream.StreamingEngine.step` launches —
    ``max_sessions`` slots padded to the shard multiple, S chains each,
    the materialized zero-state pytree — so compiling them compiles the
    serving graph.  ``dtype`` is the chunk dtype (default float32).
    """
    import jax.numpy as jnp
    import numpy as np

    dtype = np.dtype(np.float32 if dtype is None else dtype)
    nb = engine._slot_count(0) * engine.n_samples
    x = jnp.zeros((nb, capacity, engine.cfg.input_dim), dtype)
    rows = jnp.zeros((nb,), jnp.uint32)
    lengths = jnp.ones((nb,), jnp.int32)
    state = engine._gather_states([], dtype, n_pad=nb)
    return x, rows, lengths, state


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (p in (0, 100]); 0.0 on an empty sequence.

    The SLO arithmetic used by ``summarize`` and the co-design controller —
    one definition so "p95 tick latency" means the same thing in the
    decision trail, the benchmark and the tests.
    """
    vals = sorted(values)
    if not vals:
        return 0.0
    k = max(0, min(len(vals) - 1, math.ceil(p / 100.0 * len(vals)) - 1))
    return vals[k]


def summarize(metrics: Sequence[TickMetrics]) -> dict:
    """Aggregate control-plane observables over recorded ticks.

    The engine's ``metrics`` list is the single source of truth (the
    scheduler holds no copy); feed it here for the roll-up an operator or
    autoscaler wants: pad waste, distinct launch shapes (compiled-graph
    count), queue depth, chain-timesteps/sec.  Latency and throughput come
    as p50/p95 too, not just means — an SLO is a tail guarantee, and the
    mean hides exactly the slow ticks the controller must react to.

    Fleet trails carry tenant-tagged records (``TickMetrics.tenant``).
    When any are present the roll-up gains a ``"tenants"`` key: per-tenant
    sub-summaries over that tenant's own records, so each tenant's SLO
    (queue_wait_s_p95, duration_s_p95, dropped) is read off its own slice
    rather than the fleet mix.
    """
    if not metrics:
        return {"ticks": 0}
    live = sum(m.live_chain_steps for m in metrics)
    padded = sum(m.padded_steps for m in metrics)
    dur = sum(m.duration_s for m in metrics)
    durs = [m.duration_s for m in metrics]
    tps = [m.tokens_per_sec for m in metrics]
    out = {
        "ticks": len(metrics),
        "capacities_used": sorted({m.capacity for m in metrics}),
        "live_chain_steps": live,
        "padded_steps": padded,
        "pad_waste": 1.0 - live / padded if padded else 0.0,
        "mean_queue_depth": (sum(m.queue_depth for m in metrics)
                             / len(metrics)),
        "tokens_per_sec": live / dur if dur > 0 else 0.0,
        "duration_s_p50": percentile(durs, 50),
        "duration_s_p95": percentile(durs, 95),
        "tokens_per_sec_p50": percentile(tps, 50),
        "tokens_per_sec_p95": percentile(tps, 95),
        "queue_wait_s_p95": percentile([m.queue_wait_s for m in metrics], 95),
        "compiles": sum(m.compiles for m in metrics),
        "dropped": sum(m.dropped for m in metrics),
        # Early-exit observables: how many chains the store still runs
        # (mean over the window — a gauge, not a counter) and how many
        # rows convergence retired in total.  active_chains_mean equal to
        # live sessions x S means early exit never fired (or is off).
        "active_chains_mean": (sum(m.active_chains for m in metrics)
                               / len(metrics)),
        "reclaimed_rows": sum(m.reclaimed_rows for m in metrics),
        # Distill observables: rows on the single-chain fast path (gauge —
        # mean over the window) and total MC escalations (counter).
        "student_rows_mean": (sum(m.student_rows for m in metrics)
                              / len(metrics)),
        "escalations": sum(m.escalations for m in metrics),
    }
    tenants = sorted({m.tenant for m in metrics if m.tenant is not None})
    if tenants:
        # Sub-summaries see tenant-stripped copies — a tagged record must
        # not spawn a second "tenants" level inside its own slice.
        out["tenants"] = {
            name: summarize([dataclasses.replace(m, tenant=None)
                             for m in metrics if m.tenant == name])
            for name in tenants}
    return out
