"""Control plane: durable persistence, async admission, adaptive scheduling.

The two ISSUE 3 acceptance invariants live here:

* **kill-and-restore invariance** — a stream snapshotted mid-session and
  resumed in a fresh engine/store produces bit-identical per-chunk outputs
  and uncertainty summaries to the uninterrupted run, on all three
  backends, including across a ``chunk_capacity`` change at resume;
* **admission drains under churn** — 3× store capacity admitted through
  the queue with random mid-stream evictions: every session eventually
  streams to completion, no mask-row is shared by two live sessions, and
  no chunk is dropped.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ckpt import checkpoint
from repro.core import classifier as clf, mcd
from repro.serve import (AdmissionQueue, AdaptiveTickScheduler, CapacityError,
                         DrainRejected, JsonlSink, QueueFull, Session,
                         SessionStore, StreamingEngine, TickMetrics,
                         pow2_ladder, restore_store, snapshot_store,
                         summarize)
from repro.serve.scheduler import percentile

BACKENDS = ("reference", "pallas_step", "pallas_seq")


def _cfg_params(s=3, seed=3, hidden=8):
    cfg = clf.ClassifierConfig(
        hidden=hidden, num_layers=2, num_classes=4,
        mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=s, seed=seed))
    return cfg, clf.init(jax.random.key(0), cfg)


class TestAdmissionQueue:
    def test_priority_then_fifo(self):
        q = AdmissionQueue(max_pending=8)
        store = SessionStore(n_samples=1, max_sessions=3)
        q.submit("low", priority=0)
        q.submit("hi", priority=9)
        q.submit("mid-a", priority=5)
        q.submit("mid-b", priority=5)
        assert [t.sid for t in q.waiting()] == ["hi", "mid-a", "mid-b", "low"]
        admitted = q.drain(store)
        # ICU first, FIFO within the class, one left waiting
        assert [s.sid for s in admitted] == ["hi", "mid-a", "mid-b"]
        assert q.depth == 1 and "low" in q

    def test_queue_full_is_typed_backpressure(self):
        q = AdmissionQueue(max_pending=2)
        q.submit("a")
        q.submit("b")
        with pytest.raises(QueueFull, match="shed load"):
            q.submit("c")
        assert isinstance(QueueFull("x"), RuntimeError)  # callers may be old

    def test_duplicate_and_mismatched_submit(self):
        q = AdmissionQueue()
        q.submit("a")
        with pytest.raises(ValueError, match="already queued"):
            q.submit("a")
        sess = SessionStore(n_samples=1).admit("b")
        with pytest.raises(ValueError, match="sid"):
            q.submit("zzz", session=sess)

    def test_cancel_is_lazy_but_effective(self):
        q = AdmissionQueue()
        store = SessionStore(n_samples=1, max_sessions=4)
        q.submit("a", priority=2)
        q.submit("b")
        assert q.cancel("a") and not q.cancel("a")
        assert [s.sid for s in q.drain(store)] == ["b"]
        assert q.depth == 0

    def test_cancel_churn_keeps_heap_bounded(self):
        """A store pinned at capacity never drains; submit/cancel churn
        must not grow the heap (lazy deletion is compacted)."""
        q = AdmissionQueue(max_pending=4)
        for i in range(500):
            q.submit(f"s{i}")
            q.cancel(f"s{i}")
        assert q.depth == 0 and len(q._heap) <= 8

    def test_drain_reattaches_evicted_carry(self):
        store = SessionStore(n_samples=2, seed=0, max_sessions=1)
        evicted = store.admit("old")
        store.evict("old")
        store.admit("hog")
        q = AdmissionQueue()
        q.submit("old", session=evicted)
        assert q.drain(store) == []                 # no room yet
        store.evict("hog")
        (back,) = q.drain(store)
        assert back is evicted                      # same draw, same rows
        np.testing.assert_array_equal(np.asarray(back.rows), [0, 1])

    def test_drain_is_exception_safe(self):
        """Regression (ISSUE 4): a re-attach the store rejects mid-drain
        used to abort the drain — the already-admitted sessions were never
        reported and every ticket behind the bad one was starved for the
        tick.  Now the drain finishes first, then raises the typed
        DrainRejected carrying the partial result."""
        store = SessionStore(n_samples=2, seed=7, max_sessions=4)
        bad = SessionStore(n_samples=2, seed=999).admit("bad")  # wrong seed
        q = AdmissionQueue()
        q.submit("hi", priority=9)
        q.submit("bad", priority=5, session=bad)
        q.submit("low", priority=0)
        with pytest.raises(DrainRejected, match="bad") as exc_info:
            q.drain(store)
        err = exc_info.value
        assert isinstance(err, RuntimeError)        # typed, but compatible
        # both healthy tickets went live — including the one queued BEHIND
        # the bad one — and both are reported in the partial result
        assert [s.sid for s in err.admitted] == ["hi", "low"]
        assert store.active == ["hi", "low"]
        # the poison ticket is gone from the queue (it can never succeed)
        assert [(t.sid, type(e).__name__) for t, e in err.rejected] == \
            [("bad", "ValueError")]
        assert q.depth == 0 and "bad" not in q

    def test_engine_contains_drain_rejection(self):
        """The poison is the ticket owner's problem, not the caller's:
        close_session still returns the evicted carry, the healthy ticket
        behind the poison still goes live, and the reject is recorded in
        engine.dropped_admissions instead of raised at an unrelated call."""
        cfg, params = _cfg_params()
        eng = StreamingEngine(params, cfg, max_sessions=2)
        eng.open_session("live1")                    # rows 0..2, stays live
        eng.open_session("hog")                      # rows 3..5, evicted below
        # passes admit()'s eager seed/chain checks but collides on rows with
        # live1 — only SessionStore.attach can reject it, mid-drain
        clash = SessionStore(n_samples=3, seed=3).admit("clash")
        eng.admit("clash", priority=9, session=clash)
        eng.admit("ok", priority=0)                  # queued behind the poison
        evicted = eng.close_session("hog")           # triggers the drain
        assert evicted.sid == "hog"                  # carry not lost
        assert eng.active_sessions == ["live1", "ok"]
        assert eng.queued_sessions == []
        (ticket, err), = eng.dropped_admissions
        assert ticket.sid == "clash" and "collide" in str(err)

    def test_dropped_threads_through_metrics(self, tmp_path):
        """Regression (ISSUE 8): drops were visible only in the in-memory
        ``dropped_admissions`` deque — invisible to the JSONL trail and
        ``summarize``.  A mid-drain reject must land as ``dropped`` on the
        next tick's TickMetrics, serialize through JsonlSink, sum in
        summarize, and reset (not double-report) on the following tick."""
        cfg, params = _cfg_params()
        path = tmp_path / "ticks.jsonl"
        sink = JsonlSink(str(path))
        eng = StreamingEngine(params, cfg, max_sessions=2,
                              metrics_sink=sink)
        eng.open_session("live1")                    # rows 0..2
        eng.open_session("hog")
        clash = SessionStore(n_samples=3, seed=3).admit("clash")
        eng.admit("clash", priority=9, session=clash)
        eng.close_session("hog")                     # drain drops "clash"
        assert len(eng.dropped_admissions) == 1
        m1 = (eng.step({"live1": jnp.ones((2, 1))}), eng.last_metrics)[1]
        assert m1.dropped == 1
        m2 = (eng.step({"live1": jnp.ones((2, 1))}), eng.last_metrics)[1]
        assert m2.dropped == 0                       # reported once
        assert summarize(list(eng.metrics))["dropped"] == 1
        recs = [__import__("json").loads(line)
                for line in path.read_text().splitlines()]
        assert [r["dropped"] for r in recs] == [1, 0]
        assert all(r["tenant"] is None for r in recs)
        sink.close()

    def test_admit_reraises_own_tickets_rejection(self):
        """When the synchronous drain inside admit() rejects the caller's
        OWN ticket, admit must raise — returning None would read as
        'queued' while the ticket is permanently gone."""
        cfg, params = _cfg_params()
        eng = StreamingEngine(params, cfg, max_sessions=2)
        eng.open_session("live1")                    # rows 0..2
        # passes the eager seed/chain checks; only attach sees the collision
        clash = SessionStore(n_samples=3, seed=3).admit("clash")
        with pytest.raises(ValueError, match="collide"):
            eng.admit("clash", session=clash)
        assert eng.queued_sessions == []             # not silently parked
        assert len(eng.dropped_admissions) == 0      # raised, not swallowed
        assert eng.active_sessions == ["live1"]

    def test_drain_reports_multiple_rejections(self):
        store = SessionStore(n_samples=2, seed=7, max_sessions=4)
        other = SessionStore(n_samples=2, seed=999)
        q = AdmissionQueue()
        q.submit("x", priority=3, session=other.admit("x"))
        q.submit("ok")
        q.submit("y", priority=1, session=other.admit("y"))
        with pytest.raises(DrainRejected) as exc_info:
            q.drain(store)
        err = exc_info.value
        assert [s.sid for s in err.admitted] == ["ok"]
        assert sorted(t.sid for t, _ in err.rejected) == ["x", "y"]
        assert store.active == ["ok"] and q.depth == 0

    def test_store_capacity_error_stays_runtimeerror(self):
        """The typed exception contract: CapacityError subclasses
        RuntimeError so pre-PR 3 callers keep working."""
        store = SessionStore(n_samples=1, max_sessions=1)
        store.admit("a")
        with pytest.raises(RuntimeError):
            store.admit("b")
        with pytest.raises(CapacityError):
            store.attach(SessionStore(n_samples=1).admit("c"))


class TestScheduler:
    def test_pow2_ladder(self):
        assert pow2_ladder(512) == (8, 16, 32, 64, 128, 256, 512)
        assert pow2_ladder(100) == (8, 16, 32, 64, 100)
        assert pow2_ladder(1) == (1,)

    def test_pow2_ladder_honors_max_capacity(self):
        """Regression (ISSUE 4): pow2_ladder(4) returned (8,) — a single
        rung *above* the operator's cap, so the scheduler silently accepted
        chunks longer than the stated maximum.  No rung may exceed the cap,
        and the top rung must equal it (chunks up to the cap still fit)."""
        assert pow2_ladder(4) == (4,)
        for cap in (1, 3, 4, 7, 8, 9, 100, 512):
            ladder = pow2_ladder(cap)
            assert ladder[-1] == cap
            assert all(r <= cap for r in ladder)
            assert list(ladder) == sorted(set(ladder))
        # and the scheduler built on it now rejects what the operator capped
        s = AdaptiveTickScheduler(pow2_ladder(4))
        assert s.max_capacity == 4 and s.plan([4]) == 4
        with pytest.raises(ValueError, match="ladder"):
            s.plan([5])

    def test_rung_tracks_the_window(self):
        s = AdaptiveTickScheduler((4, 16, 64), window=4)
        assert s.plan([3, 2]) == 4
        assert s.plan([10]) == 16
        # windowed max keeps the rung up while the burst is in view
        assert s.plan([2]) == 16
        for _ in range(4):
            s.plan([2])
        assert s.plan([2]) == 4                     # burst aged out

    def test_current_tick_always_covered(self):
        s = AdaptiveTickScheduler((4, 16, 64), percentile=50.0, window=64)
        for _ in range(10):
            s.plan([2])
        assert s.plan([2, 60]) == 64                # outlier climbs anyway

    def test_over_ladder_rejected(self):
        s = AdaptiveTickScheduler((4, 8))
        with pytest.raises(ValueError, match="ladder"):
            s.plan([9])

    def test_state_roundtrip(self):
        s = AdaptiveTickScheduler((4, 16, 64), window=8)
        s.plan([10, 3])
        s2 = AdaptiveTickScheduler((4, 16, 64), window=8)
        s2.load_state(s.state())
        assert s2.plan([2]) == 16                   # remembers the 10

    def test_engine_auto_bounds_shapes_and_matches_dynamic(self):
        """chunk_capacity='auto' serves bit-identically to dynamic mode and
        compiles at most len(ladder) shapes; metrics are emitted per tick."""
        cfg, params = _cfg_params()
        T = 11
        sig = jax.random.normal(jax.random.key(1), (T, 1))
        dyn = StreamingEngine(params, cfg, max_sessions=2)
        aut = StreamingEngine(params, cfg, max_sessions=2,
                              chunk_capacity="auto", ladder=(4, 8))
        for eng in (dyn, aut):
            eng.open_session("a")
        want = got = None
        for a, b in ((0, 4), (4, 5), (5, T)):
            want = dyn.step({"a": sig[a:b]})["a"]
            got = aut.step({"a": sig[a:b]})["a"]
        np.testing.assert_array_equal(np.asarray(got.summary.probs),
                                      np.asarray(want.summary.probs))
        assert aut.tick == 3 and len(aut.metrics) == 3
        caps = {m.capacity for m in aut.metrics}
        assert caps <= {4, 8}
        m = aut.last_metrics
        assert m.queue_depth == 0 and 0.0 <= m.pad_waste < 1.0
        assert m.live_steps == T - 5 and m.tokens_per_sec > 0
        assert m.live_chain_steps == m.live_steps * cfg.mcd.n_samples
        agg = summarize(aut.metrics)
        assert agg["ticks"] == 3 and set(agg["capacities_used"]) == caps
        assert agg["live_chain_steps"] == T * cfg.mcd.n_samples
        assert 0.0 <= agg["pad_waste"] < 1.0
        assert summarize([]) == {"ticks": 0}

    def test_metrics_window_is_bounded(self):
        cfg, params = _cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=1, metrics_window=2)
        eng.open_session("a")
        for _ in range(4):
            eng.step({"a": jnp.ones((2, 1))})
        assert len(eng.metrics) == 2 and eng.tick == 4
        assert eng.last_metrics.tick == 3

    def test_percentile_is_nearest_rank(self):
        vals = list(range(1, 21))                   # 1..20
        assert percentile(vals, 50) == 10
        assert percentile(vals, 95) == 19
        assert percentile(vals, 100) == 20
        assert percentile([7.0], 95) == 7.0
        assert percentile([], 95) == 0.0

    def test_summarize_reports_tail_latency(self):
        def m(i, dur):
            return TickMetrics(tick=i, capacity=4, n_chunks=1, live_rows=2,
                               batch_rows=2, queue_depth=0, live_steps=4,
                               live_chain_steps=8, padded_steps=8,
                               pad_waste=0.0, duration_s=dur,
                               tokens_per_sec=8 / dur,
                               queue_wait_s=0.1 * i, compiles=i % 2)
        agg = summarize([m(i, dur) for i, dur in
                         enumerate([1.0] * 19 + [100.0])])
        # the mean would hide the one 100 s tick; the tail must not
        assert agg["duration_s_p50"] == 1.0
        assert agg["duration_s_p95"] == 1.0
        assert agg["duration_s_p95"] < 100.0 <= max(
            [1.0] * 19 + [100.0])
        assert summarize([m(i, 100.0) for i in range(20)])[
            "duration_s_p95"] == 100.0
        assert agg["tokens_per_sec_p50"] == 8.0
        assert agg["queue_wait_s_p95"] == pytest.approx(1.8)
        assert agg["compiles"] == 10

    def test_tick_metrics_thread_queue_wait_and_compiles(self):
        # hidden=6 gives this test its own jit shape family, so the first
        # tick *must* register fresh backend compiles whatever ran before.
        cfg, params = _cfg_params(s=5, hidden=6)
        eng = StreamingEngine(params, cfg, max_sessions=1, chunk_capacity=4)
        eng.open_session("a")
        eng.admit("b")                              # waits: store is full
        m1 = (eng.step({"a": jnp.ones((4, 1))}), eng.last_metrics)[1]
        assert m1.compiles >= 1                     # cold graph, counted
        assert m1.queue_depth == 1
        assert m1.queue_wait_s > 0.0                # b has been waiting
        m2 = (eng.step({"a": jnp.ones((4, 1))}), eng.last_metrics)[1]
        assert m2.compiles == 0                     # warm graph, same shape
        assert m2.queue_wait_s > m1.queue_wait_s    # b is still waiting

    def test_jsonl_sink_flushes_per_record(self, tmp_path):
        # the trail must be readable after a crash — i.e. *before* close()
        path = tmp_path / "ticks.jsonl"
        sink = JsonlSink(str(path))
        sink.emit(TickMetrics(tick=0, capacity=4, n_chunks=1, live_rows=2,
                              batch_rows=2, queue_depth=0, live_steps=4,
                              live_chain_steps=8, padded_steps=8,
                              pad_waste=0.0, duration_s=0.5,
                              tokens_per_sec=16.0))
        lines = path.read_text().splitlines()       # no close(), no flush()
        assert len(lines) == 1
        rec = __import__("json").loads(lines[0])
        assert rec["tick"] == 0 and rec["queue_wait_s"] == 0.0
        assert rec["compiles"] == 0                 # new fields serialize
        sink.close()


class TestPersistence:
    def _store_with_state(self, s=2, hid=4, layers=2):
        store = SessionStore(n_samples=s, seed=5, max_sessions=4)
        a = store.admit("a")                        # fresh, no carry yet
        b = store.admit("b")
        b.state = [(jnp.arange(s * hid, dtype=jnp.bfloat16).reshape(s, hid),
                    jnp.arange(s * hid, dtype=jnp.float32).reshape(s, hid)
                    * 0.5) for _ in range(layers)]
        b.steps, b.chunks = 17, 3
        return store, a, b

    def test_snapshot_restore_bit_exact(self, tmp_path):
        store, _, b = self._store_with_state()
        path = snapshot_store(str(tmp_path), store)
        assert path.endswith("step-0000000000")
        got, meta = restore_store(str(tmp_path))
        assert meta["seed"] == 5 and got.active == ["a", "b"]
        assert got.next_row == store.next_row       # allocator survives
        ga, gb = got.get("a"), got.get("b")
        assert ga.fresh and gb.steps == 17 and gb.chunks == 3
        np.testing.assert_array_equal(np.asarray(gb.rows),
                                      np.asarray(b.rows))
        for (h, c), (h0, c0) in zip(gb.state, b.state):
            assert h.dtype == h0.dtype and c.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(h, jnp.float32),
                                          np.asarray(h0, jnp.float32))
            np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))

    def test_restore_subset_burns_unrestored_rows(self, tmp_path):
        store, _, _ = self._store_with_state()
        snapshot_store(str(tmp_path), store)
        got, _ = restore_store(str(tmp_path), sids=["b"])
        assert got.active == ["b"]
        # 'a' was shed, but its rows stay burned: the next admission must
        # not repeat a pre-crash Bayesian draw
        fresh_rows = np.asarray(got.admit("new").rows)
        assert fresh_rows.min() >= store.next_row
        with pytest.raises(KeyError, match="no session"):
            restore_store(str(tmp_path), sids=["ghost"])

    def test_queue_roundtrip_preserves_order_and_carry(self, tmp_path):
        store, _, _ = self._store_with_state()
        q = AdmissionQueue()
        evicted = store.evict("b")                  # carries live state
        q.submit("b", priority=1, session=evicted)
        q.submit("c", priority=7)
        snapshot_store(str(tmp_path), store, queue=q)
        q2 = AdmissionQueue()
        got, _ = restore_store(str(tmp_path), queue=q2)
        assert [t.sid for t in q2.waiting()] == ["c", "b"]
        ticket = {t.sid: t for t in q2.waiting()}["b"]
        assert ticket.session is not None and ticket.session.steps == 17
        for (h, c), (h0, c0) in zip(ticket.session.state, evicted.state):
            np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
        q2.drain(got)                               # both go live, c first
        assert got.active == ["a", "c", "b"]

    def test_sids_filter_covers_the_wait_list(self, tmp_path):
        """The sids= filter selects fresh wait-list entries too (they carry
        no arrays) and excludes unselected ones of either kind."""
        store, _, _ = self._store_with_state()
        q = AdmissionQueue()
        q.submit("fresh-q", priority=2)
        snapshot_store(str(tmp_path), store, queue=q)
        q2 = AdmissionQueue()
        got, _ = restore_store(str(tmp_path), sids=["a", "fresh-q"],
                               queue=q2)
        assert got.active == ["a"]
        assert [t.sid for t in q2.waiting()] == ["fresh-q"]
        q3 = AdmissionQueue()
        got3, _ = restore_store(str(tmp_path), sids=["b"], queue=q3)
        assert got3.active == ["b"] and q3.depth == 0
        # selecting a wait-list sid without a queue to put it in would
        # silently drop it — refuse instead (sids-filtered or not)
        with pytest.raises(ValueError, match="queue"):
            restore_store(str(tmp_path), sids=["fresh-q"])
        with pytest.raises(ValueError, match="silently drop"):
            restore_store(str(tmp_path))

    def test_aliasing_sids_never_cross_contaminate(self, tmp_path):
        """'ward 3' and 'ward_3' sanitize to the same checkpoint leaf name;
        the recorded per-sid keys keep a partial restore addressing the
        right patient's carry."""
        store = SessionStore(n_samples=1, seed=0, max_sessions=4)
        for sid, fill in (("ward 3", 1.0), ("ward_3", 2.0)):
            sess = store.admit(sid)
            sess.state = [(jnp.full((1, 4), fill),
                           jnp.full((1, 4), fill, jnp.float32))]
            sess.steps = int(fill)
        snapshot_store(str(tmp_path), store)
        for sid, fill in (("ward 3", 1.0), ("ward_3", 2.0)):
            got, _ = restore_store(str(tmp_path), sids=[sid])
            h, c = got.get(sid).state[0]
            np.testing.assert_array_equal(np.asarray(c),
                                          np.full((1, 4), fill, np.float32))
            np.testing.assert_array_equal(np.asarray(got.get(sid).rows),
                                          np.asarray(store.get(sid).rows))

    def test_h_only_carry_roundtrips(self, tmp_path):
        """GRU sessions store (h,) 1-tuples per layer — the snapshot format
        records the carry arity and restores the same pytree shape."""
        store = SessionStore(n_samples=2, seed=5, max_sessions=2)
        g = store.admit("g")
        g.state = [(jnp.arange(8, dtype=jnp.bfloat16).reshape(2, 4),)
                   for _ in range(3)]
        g.steps, g.chunks = 9, 2
        snapshot_store(str(tmp_path), store)
        got, meta = restore_store(str(tmp_path))
        assert meta["sessions"]["g"]["parts"] == 1
        gg = got.get("g")
        assert [len(layer) for layer in gg.state] == [1, 1, 1]
        for (h,), (h0,) in zip(gg.state, g.state):
            assert h.dtype == h0.dtype
            np.testing.assert_array_equal(np.asarray(h, jnp.float32),
                                          np.asarray(h0, jnp.float32))

    def test_snapshot_steps_are_monotone_and_prunable(self, tmp_path):
        store, _, _ = self._store_with_state()
        p0 = snapshot_store(str(tmp_path), store)
        p1 = snapshot_store(str(tmp_path), store)
        assert p0 != p1 and checkpoint.latest_step(str(tmp_path)) == 1
        checkpoint.keep_last(str(tmp_path), 1)
        got, meta = restore_store(str(tmp_path))
        assert meta["step"] == 1 and got.active == ["a", "b"]

    def test_corrupt_snapshot_detected(self, tmp_path):
        import os
        store, _, _ = self._store_with_state()
        path = snapshot_store(str(tmp_path), store)
        victim = [f for f in os.listdir(path) if f.endswith(".npy")][0]
        with open(os.path.join(path, victim), "r+b") as f:
            f.seek(-1, 2)
            f.write(b"\x7f")
        with pytest.raises(IOError, match="checksum"):
            restore_store(str(tmp_path))


class TestKillRestoreInvariance:
    """Acceptance: snapshot mid-session + resume in a fresh engine ==
    the uninterrupted stream, bit-identically."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_kill_restore_bit_identical(self, backend, tmp_path):
        cfg, params = _cfg_params()
        T = 10
        sig_a = jax.random.normal(jax.random.key(1), (T, 1))
        sig_b = jax.random.normal(jax.random.key(2), (T, 1))

        gold = StreamingEngine(params, cfg, backend=backend, max_sessions=2)
        gold.open_session("a")
        gold.open_session("b")
        gold.step({"a": sig_a[:4], "b": sig_b[:6]})
        want = gold.step({"a": sig_a[4:], "b": sig_b[6:]})

        victim = StreamingEngine(params, cfg, backend=backend,
                                 max_sessions=2)
        victim.open_session("a")
        victim.open_session("b")
        victim.step({"a": sig_a[:4], "b": sig_b[:6]})
        victim.snapshot(str(tmp_path), extra={"note": "pre-crash"})
        del victim                                   # the crash

        revived = StreamingEngine(params, cfg, backend=backend,
                                  max_sessions=2)
        assert revived.restore(str(tmp_path)) == {"note": "pre-crash"}
        assert sorted(revived.active_sessions) == ["a", "b"]
        got = revived.step({"a": sig_a[4:], "b": sig_b[6:]})
        for sid in ("a", "b"):
            assert got[sid].steps_total == want[sid].steps_total == T
            np.testing.assert_array_equal(
                np.asarray(got[sid].summary.probs),
                np.asarray(want[sid].summary.probs))
            np.testing.assert_array_equal(
                np.asarray(got[sid].summary.mutual_information),
                np.asarray(want[sid].summary.mutual_information))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_gru_kill_restore_bit_identical(self, backend, tmp_path):
        """GRU parity for the acceptance invariant: h-only carries snapshot
        and restore bit-identically on every backend."""
        cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, num_classes=4, cell="gru",
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=3, seed=3))
        params = clf.init(jax.random.key(0), cfg)
        T = 10
        sig = jax.random.normal(jax.random.key(1), (T, 1))

        gold = StreamingEngine(params, cfg, backend=backend, max_sessions=1)
        gold.open_session("a")
        gold.step({"a": sig[:4]})
        want = gold.step({"a": sig[4:]})["a"]

        victim = StreamingEngine(params, cfg, backend=backend,
                                 max_sessions=1)
        victim.open_session("a")
        victim.step({"a": sig[:4]})
        victim.snapshot(str(tmp_path))
        del victim                                   # the crash

        revived = StreamingEngine(params, cfg, backend=backend,
                                  max_sessions=1)
        revived.restore(str(tmp_path))
        got = revived.step({"a": sig[4:]})["a"]
        assert got.steps_total == want.steps_total == T
        np.testing.assert_array_equal(np.asarray(got.summary.probs),
                                      np.asarray(want.summary.probs))
        np.testing.assert_array_equal(
            np.asarray(got.summary.mutual_information),
            np.asarray(want.summary.mutual_information))

    def test_restore_refuses_cell_mismatch(self, tmp_path):
        """LSTM (h, c) carries must not resume into a GRU engine (or vice
        versa) — the pytrees are not interchangeable."""
        cfg, params = _cfg_params()
        eng = StreamingEngine(params, cfg, max_sessions=1)
        eng.open_session("a")
        eng.snapshot(str(tmp_path))
        g_cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, num_classes=4, cell="gru",
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=3, seed=3))
        with pytest.raises(ValueError, match="cell|gru|lstm"):
            StreamingEngine(clf.init(jax.random.key(0), g_cfg), g_cfg,
                            max_sessions=1).restore(str(tmp_path))

    @pytest.mark.parametrize("capacity", [8, "auto"])
    def test_restore_across_chunk_capacity_change(self, capacity, tmp_path):
        """The snapshotting process ran dynamic shapes; the restoring one
        runs fixed/adaptive — per-chunk outputs stay bit-identical (the
        lengths-pinned graph family is launch-shape independent)."""
        cfg, params = _cfg_params()
        T = 9
        sig = jax.random.normal(jax.random.key(4), (T, 1))
        gold = StreamingEngine(params, cfg, max_sessions=2)
        gold.open_session("x")
        gold.step({"x": sig[:5]})
        want = gold.step({"x": sig[5:]})["x"]

        victim = StreamingEngine(params, cfg, max_sessions=2)
        victim.open_session("x")
        victim.step({"x": sig[:5]})
        victim.snapshot(str(tmp_path))
        revived = StreamingEngine(params, cfg, max_sessions=2,
                                  chunk_capacity=capacity)
        revived.restore(str(tmp_path))
        got = revived.step({"x": sig[5:]})["x"]
        np.testing.assert_array_equal(np.asarray(got.summary.probs),
                                      np.asarray(want.summary.probs))

    def test_admit_of_live_sid_rejected_eagerly(self):
        cfg, params = _cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=2)
        eng.admit("a")
        with pytest.raises(ValueError, match="already admitted"):
            eng.admit("a")

    def test_admit_validates_reattach_ticket_eagerly(self):
        """A statically-mismatched re-attach must fail at admit(), not
        blow up whichever later step()/close_session() drains it (and
        cost that caller the evicted carry)."""
        cfg, params = _cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=1)
        eng.admit("hog")
        bad_seed = SessionStore(n_samples=2, seed=999).admit("x")
        with pytest.raises(ValueError, match="seed"):
            eng.admit("x", session=bad_seed)
        bad_s = SessionStore(n_samples=5, seed=cfg.mcd.seed).admit("y")
        with pytest.raises(ValueError, match="chains"):
            eng.admit("y", session=bad_s)
        assert eng.queued_sessions == []            # nothing latent queued
        sess = eng.close_session("hog")             # still returns the carry
        assert sess.sid == "hog"

    def test_restore_holds_a_wait_list_larger_than_max_pending(self,
                                                               tmp_path):
        """Crash recovery must not depend on the relaunch flags: a snapshot
        whose wait-list exceeds this process's max_pending still restores
        (the replacement queue is sized from the snapshot)."""
        cfg, params = _cfg_params(s=2)
        big = StreamingEngine(params, cfg, max_sessions=1, max_pending=8)
        big.admit("live")
        for k in range(5):
            big.admit(f"w{k}")
        big.snapshot(str(tmp_path))
        small = StreamingEngine(params, cfg, max_sessions=1, max_pending=2)
        small.restore(str(tmp_path))
        assert small.active_sessions == ["live"]
        assert len(small.queued_sessions) == 5

    def test_restore_refuses_changed_dropout_config(self, tmp_path):
        """p/placement change the mask values under the same (seed, rows);
        resuming across them must be an error, not silent divergence."""
        cfg, params = _cfg_params()
        eng = StreamingEngine(params, cfg, max_sessions=1)
        eng.open_session("a")
        eng.snapshot(str(tmp_path))
        p_cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, num_classes=4,
            mcd=mcd.MCDConfig(p=0.25, placement="YN", n_samples=3, seed=3))
        with pytest.raises(ValueError, match="masks"):
            StreamingEngine(clf.init(jax.random.key(0), p_cfg), p_cfg,
                            max_sessions=1).restore(str(tmp_path))
        b_cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, num_classes=4,
            mcd=mcd.MCDConfig(p=0.125, placement="YY", n_samples=3, seed=3))
        with pytest.raises(ValueError, match="masks"):
            StreamingEngine(clf.init(jax.random.key(0), b_cfg), b_cfg,
                            max_sessions=1).restore(str(tmp_path))

    def test_restore_refuses_mismatched_config(self, tmp_path):
        cfg, params = _cfg_params()
        eng = StreamingEngine(params, cfg, max_sessions=1)
        eng.open_session("a")
        eng.snapshot(str(tmp_path))
        other_cfg, other_params = _cfg_params(s=4)
        with pytest.raises(ValueError, match="chains"):
            StreamingEngine(other_params, other_cfg,
                            max_sessions=1).restore(str(tmp_path))
        seed_cfg, seed_params = _cfg_params(seed=99)
        with pytest.raises(ValueError, match="seed"):
            StreamingEngine(seed_params, seed_cfg,
                            max_sessions=1).restore(str(tmp_path))
        with pytest.raises(RuntimeError, match="fresh engine"):
            eng.restore(str(tmp_path))

    def test_attach_roundtrips_through_ckpt(self, tmp_path):
        """Satellite: evict -> repro.ckpt save -> load in a fresh store ->
        attach -> the stream finishes bit-identically, on every backend."""
        cfg, params = _cfg_params(s=2)
        T = 8
        sig = jax.random.normal(jax.random.key(6), (T, 1))
        for backend in BACKENDS:
            solo = StreamingEngine(params, cfg, backend=backend,
                                   max_sessions=1)
            solo.open_session("a")
            want = solo.step({"a": sig})["a"]

            eng = StreamingEngine(params, cfg, backend=backend,
                                  max_sessions=1)
            eng.open_session("a")
            eng.step({"a": sig[:3]})
            evicted = eng.close_session("a")
            d = str(tmp_path / backend)
            checkpoint.save(d, 0, {
                "rows": np.asarray(evicted.rows),
                "state": [[np.asarray(h), np.asarray(c)]
                          for h, c in evicted.state]},
                meta={"steps": evicted.steps, "chunks": evicted.chunks,
                      "seed": cfg.mcd.seed})
            like = {"rows": 0,
                    "state": [[0, 0] for _ in evicted.state]}
            arrays = checkpoint.restore(d, 0, like)
            m = checkpoint.load_meta(d, 0)
            thawed = Session(
                sid="a", rows=jnp.asarray(arrays["rows"]), seed=m["seed"],
                state=[(jnp.asarray(h), jnp.asarray(c))
                       for h, c in arrays["state"]],
                steps=m["steps"], chunks=m["chunks"])
            fresh = StreamingEngine(params, cfg, backend=backend,
                                    max_sessions=1)
            fresh.attach_session(thawed)
            got = fresh.step({"a": sig[3:]})["a"]
            assert got.steps_total == T
            np.testing.assert_array_equal(np.asarray(got.summary.probs),
                                          np.asarray(want.summary.probs))


class TestAdmissionUnderChurn:
    def test_three_x_capacity_all_complete_no_row_reuse(self):
        """Acceptance: 3x store capacity admitted through the queue with
        random mid-stream evictions (each re-queued as a re-attach).  Every
        session streams to completion, live rows never overlap, and every
        submitted chunk produces a result."""
        cfg, params = _cfg_params(s=2)
        capacity, total, T, chunk = 2, 6, 6, 2
        eng = StreamingEngine(params, cfg, max_sessions=capacity,
                              max_pending=2 * total)
        sigs = {f"s{k}": jax.random.normal(jax.random.key(10 + k), (T, 1))
                for k in range(total)}
        for k in range(total):
            eng.admit(f"s{k}", priority=k % 3)
        assert len(eng.active_sessions) == capacity
        assert len(eng.queued_sessions) == total - capacity

        rng = np.random.default_rng(0)
        served: dict[str, int] = {sid: 0 for sid in sigs}
        results_count = 0
        done: set[str] = set()
        guard = 0
        while len(done) < total:
            guard += 1
            assert guard < 200, "churn loop failed to converge"
            live = list(eng.active_sessions)
            # live sessions must never share mask rows
            rows = [tuple(np.asarray(eng.store.get(s).rows)) for s in live]
            flat = [r for rr in rows for r in rr]
            assert len(flat) == len(set(flat)), "row reuse while live"
            chunks = {}
            for sid in live:
                pos = eng.store.get(sid).steps
                if pos < T:
                    chunks[sid] = sigs[sid][pos:pos + chunk]
            results = eng.step(chunks)
            assert sorted(results) == sorted(chunks), "dropped chunks"
            results_count += len(results)
            for sid in chunks:
                served[sid] += int(results[sid].length)
            # random eviction churn: a victim loses its row mid-stream and
            # rejoins the wait-list with its carry (same Bayesian draw)
            live = list(eng.active_sessions)
            if live and rng.random() < 0.5:
                victim = live[int(rng.integers(len(live)))]
                sess = eng.close_session(victim)
                if sess.steps < T:
                    eng.admit(victim, priority=9, session=sess)
                else:
                    done.add(victim)
            for sid in list(eng.active_sessions):
                if eng.store.get(sid).steps >= T:
                    eng.close_session(sid)
                    done.add(sid)

        assert served == {sid: T for sid in sigs}
        assert len(eng.queued_sessions) == 0 and len(eng.active_sessions) == 0
        assert results_count * chunk >= total * T   # every chunk answered

class TestPrewarm:
    """ISSUE 5 satellite: boot-time compilation of the capacity ladder —
    post-warm ticks must trigger **zero** new stack-graph compiles."""

    @staticmethod
    def _stack_cache_sizes():
        from repro.kernels import ops
        return (ops.lstm_stack_layer._cache_size(),
                ops.fused_lstm_seq._cache_size(),
                ops.fused_lstm_layer._cache_size(),
                ops.gru_stack_layer._cache_size(),
                ops.fused_gru_seq._cache_size())

    def test_prewarm_then_zero_new_compiles(self):
        from repro.serve import prewarm
        cfg, params = _cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=2,
                              chunk_capacity="auto", ladder=(4, 8))
        assert prewarm(eng) == [4, 8]
        warm = self._stack_cache_sizes()
        eng.open_session("a")
        eng.open_session("b")
        sig = jax.random.normal(jax.random.key(4), (8, 1))
        for a, b in ((3, 2), (8, 4), (1, 1), (5, 8)):   # both rungs, ragged
            eng.step({"a": sig[:a], "b": sig[:b]})
        assert self._stack_cache_sizes() == warm, \
            "a post-warm tick compiled a new stack graph"
        assert {m.capacity for m in eng.metrics} == {4, 8}

    def test_prewarm_fixed_capacity_single_rung(self):
        from repro.serve import prewarm
        cfg, params = _cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=2, chunk_capacity=6)
        assert prewarm(eng) == [6]
        warm = self._stack_cache_sizes()
        eng.open_session("a")
        for n in (2, 6, 1):
            eng.step({"a": jnp.ones((n, 1), jnp.float32)})
        assert self._stack_cache_sizes() == warm

    def test_prewarm_rejects_dynamic_shapes(self):
        from repro.serve import prewarm
        cfg, params = _cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=2)  # dynamic mode
        with pytest.raises(ValueError, match="bounded"):
            prewarm(eng)


class TestMetricsSinks:
    """ISSUE 5 satellite: the per-tick metrics stream is a pluggable sink
    (bounded ring by default, JSONL file for a durable trail)."""

    def test_default_ring_sink_backs_metrics_property(self):
        from repro.serve import RingBufferSink
        cfg, params = _cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=1, metrics_window=2)
        assert isinstance(eng.metrics_sink, RingBufferSink)
        eng.open_session("a")
        for _ in range(4):
            eng.step({"a": jnp.ones((2, 1))})
        assert len(eng.metrics) == 2 and eng.last_metrics.tick == 3

    def test_jsonl_sink_writes_parseable_trail(self, tmp_path):
        import json

        from repro.serve import JsonlSink
        cfg, params = _cfg_params(s=2)
        path = tmp_path / "ticks.jsonl"
        eng = StreamingEngine(params, cfg, max_sessions=1,
                              metrics_sink=JsonlSink(str(path)))
        eng.open_session("a")
        for n in (3, 1, 2):
            eng.step({"a": jnp.ones((n, 1))})
        eng.metrics_sink.close()
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert [m["tick"] for m in lines] == [0, 1, 2]
        assert [m["live_steps"] for m in lines] == [3, 1, 2]
        assert all(m["shards"] == 1 for m in lines)
        # the ring window still serves the in-process observables
        assert len(eng.metrics) == 3
        assert summarize(eng.metrics)["ticks"] == 3
        # appending across engine restarts keeps the trail monotone
        eng2 = StreamingEngine(params, cfg, max_sessions=1,
                               metrics_sink=JsonlSink(str(path)))
        eng2.open_session("a")
        eng2.step({"a": jnp.ones((1, 1))})
        eng2.metrics_sink.close()
        assert len(path.read_text().splitlines()) == 4
