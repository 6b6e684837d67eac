"""Streaming session serving: carried state, chunk invariance, the engine.

The load-bearing invariant (ISSUE 2 acceptance): decoding an unbounded
signal chunk-by-chunk with carried ``(h, c)`` — through any backend — is
bit-identical to one full-sequence pass, for arbitrary chunk boundaries
including length-1 chunks, with the MC masks tied across the *whole*
session.  Streaming passes always supply ``lengths``; that graph family is
bit-stable across launch sizes, splits, batch composition and backends
(see docs/kernels.md), which is what makes exact assertions possible here.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autoencoder as ae, classifier as clf, distill, mcd, rnn
from repro.core.uncertainty import classification_summary
from repro.kernels import quantize
from repro.serve import (CapacityError, JsonlSink, SessionStore,
                         StreamingEngine, prewarm)

import conformance

BACKENDS = ("reference", "pallas_step", "pallas_seq")


def _stack(hiddens=(16, 16, 16), in_dim=4, placement="YNY", seed=5, key=0):
    cfg = mcd.MCDConfig(p=0.125, placement=placement, seed=seed)
    params = rnn.init_stack(jax.random.key(key), in_dim, hiddens)
    return cfg, params


def _masks(cfg, rows, in_dim, hiddens, backend):
    if backend == "reference":
        return rnn.sample_stack_masks(cfg, rows, in_dim, hiddens)
    return rnn.stack_mask_plan(cfg, len(hiddens))


def _full(n, b=6):
    return jnp.full((b,), n, jnp.int32)


class TestRunStackStreaming:
    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("splits", [[5, 12], [1] * 17, [3, 1, 6, 7]])
    def test_chunked_equals_unchunked_bit_identical(self, backend, splits):
        """Any split of T=17 (incl. all-ones) == one pass, exactly."""
        hiddens = (16, 16, 16)
        cfg, params = _stack(hiddens)
        B, T = 6, 17
        x = jax.random.normal(jax.random.key(1), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        masks = _masks(cfg, rows, 4, hiddens, backend)
        full, st_full = rnn.run_stack(params, x, masks, cfg.p,
                                      backend=backend, rows=rows,
                                      seed=cfg.seed, lengths=_full(T),
                                      return_all_states=True)

        def step(xc, state):
            return rnn.run_stack(params, xc, masks, cfg.p, backend=backend,
                                 rows=rows, seed=cfg.seed,
                                 initial_state=state,
                                 lengths=_full(xc.shape[1]),
                                 return_all_states=True)

        outs, state = conformance.chunked_run(step, x, splits)
        np.testing.assert_array_equal(np.asarray(outs), np.asarray(full))
        conformance.assert_states_equal(state, st_full, f"{backend} {splits}")

    def test_pallas_seq_chunked_equals_reference_full(self):
        """The acceptance bullet: chunked pallas_seq streaming == a single
        full-sequence *reference* pass, bit-identical."""
        hiddens = (16, 16, 16)
        cfg, params = _stack(hiddens)
        B, T = 6, 17
        x = jax.random.normal(jax.random.key(1), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        full_ref, _ = rnn.run_stack(
            params, x, rnn.sample_stack_masks(cfg, rows, 4, hiddens), cfg.p,
            lengths=_full(T))
        plan = rnn.stack_mask_plan(cfg, 3)

        def step(xc, state):
            return rnn.run_stack(params, xc, plan, cfg.p,
                                 backend="pallas_seq", rows=rows,
                                 seed=cfg.seed, initial_state=state,
                                 lengths=_full(xc.shape[1]),
                                 return_all_states=True)

        for splits in ([5, 12], [1] * 17, [3, 1, 6, 7]):
            outs, _ = conformance.chunked_run(step, x, splits)
            np.testing.assert_array_equal(np.asarray(outs),
                                          np.asarray(full_ref))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_ragged_lengths_freeze_per_row(self, backend):
        """A ragged batch (per-row lengths, padded to max T) returns each
        row's state at its own length: live prefixes are bit-identical to
        the full-length pass of the same batch (lengths is a *data* input —
        same program, so frozen rows cannot perturb live ones), and serving
        a row alone agrees to fp tolerance (a different batch shape compiles
        a different program, so solo extraction is ulp- not bit-exact)."""
        hiddens = (8, 8)
        cfg, params = _stack(hiddens, placement="YN")
        B, T = 4, 9
        x = jax.random.normal(jax.random.key(2), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        lens = jnp.array([9, 1, 4, 6], jnp.int32)
        masks = _masks(cfg, rows, 4, hiddens, backend)
        out, states = rnn.run_stack(params, x, masks, cfg.p, backend=backend,
                                    rows=rows, seed=cfg.seed, lengths=lens,
                                    return_all_states=True)
        full, full_states = rnn.run_stack(params, x, masks, cfg.p,
                                          backend=backend, rows=rows,
                                          seed=cfg.seed, lengths=_full(T, B),
                                          return_all_states=True)
        for r in range(B):
            L = int(lens[r])
            np.testing.assert_array_equal(np.asarray(out[r, :L]),
                                          np.asarray(full[r, :L]))
            # frozen at own length: last layer's h equals its last live step
            np.testing.assert_array_equal(np.asarray(states[-1][0][r]),
                                          np.asarray(out[r, L - 1]))
            solo_masks = _masks(cfg, rows[r:r + 1], 4, hiddens, backend)
            solo, solo_states = rnn.run_stack(
                params, x[r:r + 1, :L], solo_masks, cfg.p, backend=backend,
                rows=rows[r:r + 1], seed=cfg.seed,
                lengths=jnp.full((1,), L, jnp.int32), return_all_states=True)
            np.testing.assert_allclose(np.asarray(out[r, :L]),
                                       np.asarray(solo[0]),
                                       rtol=1e-5, atol=1e-6)
            for (h1, c1), (h2, c2) in zip(states, solo_states):
                np.testing.assert_allclose(np.asarray(h1[r]),
                                           np.asarray(h2[0]),
                                           rtol=1e-5, atol=1e-6)
                np.testing.assert_allclose(np.asarray(c1[r]),
                                           np.asarray(c2[0]),
                                           rtol=1e-5, atol=1e-6)

    def test_ragged_states_agree_across_backends(self):
        """Same ragged batch through all three backends: the lengths-pinned
        graph family keeps the per-row carries bit-identical across them."""
        hiddens = (8, 8)
        cfg, params = _stack(hiddens, placement="YN")
        B, T = 4, 9
        x = jax.random.normal(jax.random.key(2), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        lens = jnp.array([9, 1, 4, 6], jnp.int32)
        got = {}
        for backend in BACKENDS:
            masks = _masks(cfg, rows, 4, hiddens, backend)
            _, states = rnn.run_stack(params, x, masks, cfg.p,
                                      backend=backend, rows=rows,
                                      seed=cfg.seed, lengths=lens,
                                      return_all_states=True)
            got[backend] = states
        for backend in ("pallas_step", "pallas_seq"):
            for (h1, c1), (h2, c2) in zip(got["reference"], got[backend]):
                np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
                np.testing.assert_array_equal(
                    np.asarray(c1, np.float32), np.asarray(c2, np.float32))

    def test_return_all_states_shapes_and_dtypes(self):
        hiddens = (16, 8)
        cfg, params = _stack(hiddens, placement="YY")
        B, T = 3, 5
        x = jax.random.normal(jax.random.key(3), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        _, st_ref = rnn.run_stack(params, x,
                                  rnn.sample_stack_masks(cfg, rows, 4, hiddens),
                                  cfg.p, return_all_states=True)
        _, st_seq = rnn.run_stack(params, x, rnn.stack_mask_plan(cfg, 2),
                                  cfg.p, backend="pallas_seq", rows=rows,
                                  seed=cfg.seed, return_all_states=True)
        assert len(st_ref) == len(st_seq) == 2
        for (h, c), hid in zip(st_seq, hiddens):
            assert h.shape == (B, hid) and c.shape == (B, hid)
            assert c.dtype == jnp.float32       # Pallas carries c in fp32
        for (h, c), hid in zip(st_ref, hiddens):
            assert h.shape == (B, hid) and c.dtype == x.dtype

    def test_default_return_contract_unchanged(self):
        """Without the new kwargs run_stack returns (out, (h_T, c_T)) of the
        last layer with c in the input dtype — the pre-streaming contract."""
        hiddens = (8, 8)
        cfg, params = _stack(hiddens, placement="YN")
        x = jax.random.normal(jax.random.key(4), (3, 5, 4))
        rows = jnp.arange(3, dtype=jnp.uint32)
        out, (hT, cT) = rnn.run_stack(params, x, rnn.stack_mask_plan(cfg, 2),
                                      cfg.p, backend="pallas_seq", rows=rows,
                                      seed=cfg.seed)
        assert hT.shape == (3, 8) and cT.dtype == x.dtype


class TestRunStackStreamingGru:
    """GRU parity (ISSUE 4 acceptance): chunked == unchunked bit-identical
    on all three backends, incl. carried h state and ragged lengths."""

    def _stack(self, hiddens=(16, 16, 16), placement="YNY", seed=5):
        cfg = mcd.MCDConfig(p=0.125, placement=placement, seed=seed)
        params = rnn.init_stack(jax.random.key(0), 4, hiddens, cell="gru")
        return cfg, params

    def _masks(self, cfg, rows, hiddens, backend):
        if backend == "reference":
            return rnn.sample_stack_masks(cfg, rows, 4, hiddens, cell="gru")
        return rnn.stack_mask_plan(cfg, len(hiddens))

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("splits", [[5, 12], [1] * 17, [3, 1, 6, 7]])
    def test_chunked_equals_unchunked_bit_identical(self, backend, splits):
        hiddens = (16, 16, 16)
        cfg, params = self._stack(hiddens)
        B, T = 6, 17
        x = jax.random.normal(jax.random.key(1), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        masks = self._masks(cfg, rows, hiddens, backend)
        full, st_full = rnn.run_stack(params, x, masks, cfg.p,
                                      backend=backend, rows=rows,
                                      seed=cfg.seed, lengths=_full(T),
                                      return_all_states=True, cell="gru")

        def step(xc, state):
            return rnn.run_stack(params, xc, masks, cfg.p, backend=backend,
                                 rows=rows, seed=cfg.seed,
                                 initial_state=state,
                                 lengths=_full(xc.shape[1]),
                                 return_all_states=True, cell="gru")

        outs, state = conformance.chunked_run(step, x, splits)
        np.testing.assert_array_equal(np.asarray(outs), np.asarray(full))
        conformance.assert_states_equal(state, st_full, f"gru {backend}")

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_ragged_lengths_freeze_per_row(self, backend):
        """Ragged GRU batch: each row's h comes back frozen at its own
        length, bit-identical to the full-length pass's live prefix."""
        hiddens = (8, 8)
        cfg, params = self._stack(hiddens, placement="YN")
        B, T = 4, 9
        x = jax.random.normal(jax.random.key(2), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        lens = jnp.array([9, 1, 4, 6], jnp.int32)
        masks = self._masks(cfg, rows, hiddens, backend)
        out, states = rnn.run_stack(params, x, masks, cfg.p, backend=backend,
                                    rows=rows, seed=cfg.seed, lengths=lens,
                                    return_all_states=True, cell="gru")
        full, _ = rnn.run_stack(params, x, masks, cfg.p, backend=backend,
                                rows=rows, seed=cfg.seed,
                                lengths=_full(T, B),
                                return_all_states=True, cell="gru")
        for r in range(B):
            L = int(lens[r])
            np.testing.assert_array_equal(np.asarray(out[r, :L]),
                                          np.asarray(full[r, :L]))
            np.testing.assert_array_equal(np.asarray(states[-1][0][r]),
                                          np.asarray(out[r, L - 1]))

    def test_ragged_states_agree_across_backends(self):
        hiddens = (8, 8)
        cfg, params = self._stack(hiddens, placement="YN")
        B, T = 4, 9
        x = jax.random.normal(jax.random.key(2), (B, T, 4))
        rows = jnp.arange(B, dtype=jnp.uint32)
        lens = jnp.array([9, 1, 4, 6], jnp.int32)
        got = {}
        for backend in BACKENDS:
            masks = self._masks(cfg, rows, hiddens, backend)
            _, states = rnn.run_stack(params, x, masks, cfg.p,
                                      backend=backend, rows=rows,
                                      seed=cfg.seed, lengths=lens,
                                      return_all_states=True, cell="gru")
            got[backend] = states
        for backend in ("pallas_step", "pallas_seq"):
            for (h1,), (h2,) in zip(got["reference"], got[backend]):
                np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


class TestSessionStore:
    def test_admission_rows_unique_and_stable(self):
        store = SessionStore(n_samples=4, seed=7, max_sessions=3)
        a = store.admit("a")
        b = store.admit("b")
        np.testing.assert_array_equal(np.asarray(a.rows), [0, 1, 2, 3])
        np.testing.assert_array_equal(np.asarray(b.rows), [4, 5, 6, 7])
        assert a.seed == 7 and store.get("a") is a
        assert len(store) == 2 and "a" in store

    def test_duplicate_admission_rejected(self):
        store = SessionStore(n_samples=2)
        store.admit("a")
        with pytest.raises(ValueError, match="already admitted"):
            store.admit("a")

    def test_capacity_and_eviction(self):
        store = SessionStore(n_samples=2, max_sessions=2)
        store.admit("a")
        store.admit("b")
        with pytest.raises(CapacityError):
            store.admit("c")
        evicted = store.evict("a")
        assert evicted.sid == "a" and "a" not in store
        c = store.admit("c")                       # slot freed
        # rows never reused: a new session is a new Bayesian draw
        np.testing.assert_array_equal(np.asarray(c.rows), [4, 5])

    def test_unknown_session(self):
        store = SessionStore(n_samples=2)
        with pytest.raises(KeyError, match="unknown session"):
            store.get("nope")
        with pytest.raises(KeyError, match="unknown session"):
            store.evict("nope")

    def test_attach_validates_coordinates(self):
        store = SessionStore(n_samples=2, seed=7, max_sessions=2)
        sess = store.admit("a")
        evicted = store.evict("a")
        store.attach(evicted)                       # round-trips
        assert store.get("a") is sess
        store.evict("a")
        other = SessionStore(n_samples=2, seed=8).admit("b")
        with pytest.raises(ValueError, match="seed"):
            store.attach(other)
        wrong_s = SessionStore(n_samples=3, seed=7).admit("c")
        with pytest.raises(ValueError, match="chains"):
            store.attach(wrong_s)

    def test_attach_protects_row_allocator(self):
        """Re-attaching into a fresh store (restart) must not let later
        admissions re-allocate the attached rows, nor collide with live
        sessions — shared (seed, rows) would correlate Bayesian draws."""
        old = SessionStore(n_samples=2, seed=7)
        old.admit("s0")
        saved = old.admit("s1")                      # rows [2, 3]
        fresh = SessionStore(n_samples=2, seed=7, max_sessions=4)
        fresh.attach(saved)
        nxt = fresh.admit("s2")                      # allocator bumped past 3
        np.testing.assert_array_equal(np.asarray(nxt.rows), [4, 5])
        colliding = SessionStore(n_samples=2, seed=7).admit("ghost")  # [0, 1]
        fresh.admit("s3")                            # rows [6, 7] — fine
        with pytest.raises(ValueError, match="collide"):
            # a live session in `fresh` could then share rows — refuse
            fresh2 = SessionStore(n_samples=2, seed=7, max_sessions=4)
            fresh2.admit("live")                     # rows [0, 1]
            fresh2.attach(colliding)


class TestStudentFallback:
    """``SessionStore.grow`` and the distill fallback pin (the grow
    docstring's contract): an escalated student session must stream on
    bit-identically to an always-MC session attached with the regrown
    rows and the tiled carry."""

    def test_grow_mc_appends_fresh_zero_carry_chains(self):
        store = SessionStore(n_samples=6, seed=0)
        sess = store.admit("a", n_samples=2)            # rows [0, 1]
        sess.state = [(np.full((2, 3), 5.0, np.float32),
                       np.full((2, 3), 9.0, np.float32))]
        assert store.grow("a", 5) == 3
        np.testing.assert_array_equal(np.asarray(sess.rows),
                                      [0, 1, 2, 3, 4])  # fresh, never reused
        h, c = sess.state[0]
        np.testing.assert_array_equal(np.asarray(h[:2]), 5.0 * np.ones((2, 3)))
        np.testing.assert_array_equal(np.asarray(h[2:]),
                                      np.zeros((3, 3)))  # newcomers fresh
        np.testing.assert_array_equal(np.asarray(c[2:]), np.zeros((3, 3)))
        assert store.grow("a", 5) == 0                   # no-op at target
        with pytest.raises(ValueError, match="grow target"):
            store.grow("a", 7)                           # above the ceiling
        with pytest.raises(ValueError, match="grow target"):
            store.grow("a", 4)                           # chains never shrink

    def test_grow_student_replaces_row_tiles_carry_flips_mode(self):
        store = SessionStore(n_samples=4, seed=0)
        sess = store.admit("s", mode="student")          # one flagged row
        assert sess.mode == "student"
        assert mcd.is_student_row(int(np.asarray(sess.rows)[0]))
        carry = np.arange(3.0, dtype=np.float32)[None]   # (1, H)
        sess.state = [(carry, carry + 10.0)]
        assert store.grow("s", 4) == 4
        rows = np.asarray(sess.rows)
        assert rows.shape == (4,)
        assert not any(mcd.is_student_row(int(r)) for r in rows)
        assert sess.mode == "mc"
        for part, base in zip(sess.state[0], (carry, carry + 10.0)):
            np.testing.assert_array_equal(np.asarray(part),
                                          np.tile(base, (4, 1)))
        # the det row's base id stays burned; fresh rows follow it
        later = store.admit("next")
        assert int(np.asarray(later.rows)[0]) == int(rows[-1]) + 1

    @pytest.mark.parametrize("backend", ("reference", "pallas_seq"))
    def test_escalated_session_bit_identical_to_attached_mc_twin(self,
                                                                 backend):
        """The distill fallback pin: fresh rows ⇒ fresh masks, so from the
        first post-escalation chunk the regrown session is byte-for-byte
        an always-MC session attached at the student's carry."""
        cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, num_classes=4,
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=4, seed=3))
        params = clf.init(jax.random.key(0), cfg)
        student = distill.init_student(jax.random.key(1), cfg, params)
        sig = np.asarray(jax.random.normal(jax.random.key(2), (16, 1)),
                         np.float32)

        def chunk(t):
            return {"p": jnp.asarray(sig[4 * t:4 * (t + 1)])}

        # threshold 0.0: a fresh unc head predicts softplus-positive MI on
        # any input, so the first served chunk escalates
        esc = StreamingEngine(params, cfg, backend=backend, max_sessions=1,
                              student=student,
                              student_escalate_threshold=0.0)
        esc.open_session("p", mode="student")
        esc.step(chunk(0))
        assert esc.last_metrics.escalations == 1
        sess = esc.store.get("p")
        assert sess.mode == "mc" and int(sess.rows.shape[0]) == 4

        plain = StreamingEngine(params, cfg, backend=backend, max_sessions=1)
        plain.attach_session(dataclasses.replace(
            sess, state=[tuple(layer) for layer in sess.state]))
        for t in range(1, 4):
            got = esc.step(chunk(t))["p"].summary
            want = plain.step(chunk(t))["p"].summary
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestStreamingEngine:
    def _cfg_params(self, s=3, seed=3):
        cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, num_classes=4,
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=s,
                              seed=seed))
        return cfg, clf.init(jax.random.key(0), cfg)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_ragged_cobatched_equals_solo_full(self, backend):
        """Ragged co-batched chunked serving == solo single-chunk serving,
        bit-identical per session (batch composition is invisible)."""
        cfg, params = self._cfg_params()
        T = 11
        sig_a = jax.random.normal(jax.random.key(1), (T, 1))
        sig_b = jax.random.normal(jax.random.key(2), (T, 1))
        eng = StreamingEngine(params, cfg, backend=backend, max_sessions=2)
        eng.open_session("a")
        eng.open_session("b")
        eng.step({"a": sig_a[:4], "b": sig_b[:7]})     # ragged tick
        eng.step({"a": sig_a[4:5], "b": sig_b[7:]})    # length-1 chunk for a
        ra = eng.step({"a": sig_a[5:]})["a"]           # b sits this tick out
        solo = StreamingEngine(params, cfg, backend=backend, max_sessions=1)
        solo.open_session("a")
        qa = solo.step({"a": sig_a})["a"]
        np.testing.assert_array_equal(np.asarray(ra.summary.probs),
                                      np.asarray(qa.summary.probs))
        np.testing.assert_array_equal(
            np.asarray(ra.summary.mutual_information),
            np.asarray(qa.summary.mutual_information))
        assert ra.steps_total == qa.steps_total == T

    def test_matches_direct_classifier_pass(self):
        """Engine output == a single full-sequence classifier pass on the
        reference backend (masks tied across every chunk boundary)."""
        cfg, params = self._cfg_params()
        s = cfg.mcd.n_samples
        T = 9
        sig = jax.random.normal(jax.random.key(4), (T, 1))
        eng = StreamingEngine(params, cfg, backend="pallas_seq",
                              max_sessions=1)
        eng.open_session("x")
        res = None
        for a in range(0, T, 2):                      # chunks of 2 then 1
            res = eng.step({"x": sig[a:a + 2]})["x"]
        rows = jnp.arange(s, dtype=jnp.uint32)
        logits = clf.apply(params, jnp.broadcast_to(sig[None], (s, T, 1)),
                           rows, cfg, backend="reference",
                           lengths=jnp.full((s,), T, jnp.int32))
        want = classification_summary(logits[:, None].astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(res.summary.probs),
                                      np.asarray(want.probs[0]))

    def test_autoencoder_streaming(self):
        cfg = ae.AutoencoderConfig(
            hidden=8, num_layers=1,
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=2, seed=1))
        params = ae.init(jax.random.key(0), cfg)
        eng = StreamingEngine(params, cfg, backend="pallas_seq",
                              max_sessions=2)
        eng.open_session("a")
        eng.open_session("b")
        res = eng.step({"a": jnp.ones((5, 1)), "b": jnp.zeros((3, 1))})
        assert res["a"].summary.mean.shape == (5, 1)
        assert res["b"].summary.total.shape == (3, 1)
        assert (np.asarray(res["a"].summary.total) >= 0).all()
        res2 = eng.step({"a": jnp.ones((2, 1))})
        assert res2["a"].steps_total == 7

    def test_bookkeeping_and_eviction(self):
        cfg, params = self._cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=1)
        eng.open_session("a")
        with pytest.raises(CapacityError):
            eng.open_session("b")
        eng.step({"a": jnp.ones((3, 1))})
        sess = eng.close_session("a")
        assert sess.steps == 3 and sess.chunks == 1
        assert sess.state is not None and eng.active_sessions == []
        eng.open_session("b")                          # capacity freed

    def test_evict_attach_resumes_same_draw(self):
        """close → attach continues the stream bit-identically (same state,
        same (seed, rows) coordinates — the checkpoint/restore path)."""
        cfg, params = self._cfg_params()
        T = 8
        sig = jax.random.normal(jax.random.key(6), (T, 1))
        eng = StreamingEngine(params, cfg, max_sessions=1)
        eng.open_session("a")
        eng.step({"a": sig[:3]})
        frozen = eng.close_session("a")
        eng.attach_session(frozen)
        res = eng.step({"a": sig[3:]})["a"]
        solo = StreamingEngine(params, cfg, max_sessions=1)
        solo.open_session("a")
        want = solo.step({"a": sig})["a"]
        np.testing.assert_array_equal(np.asarray(res.summary.probs),
                                      np.asarray(want.summary.probs))
        assert res.steps_total == T and frozen.chunks == 2

    def test_chunk_capacity_fixed_shapes(self):
        """Fixed-shape mode (pad to capacity + idle slots) serves the same
        results while reusing one compiled graph across ragged ticks."""
        cfg, params = self._cfg_params()
        T = 9
        sig_a = jax.random.normal(jax.random.key(1), (T, 1))
        sig_b = jax.random.normal(jax.random.key(2), (T, 1))
        fixed = StreamingEngine(params, cfg, max_sessions=3, chunk_capacity=5)
        fixed.open_session("a")
        fixed.open_session("b")
        fixed.step({"a": sig_a[:4], "b": sig_b[:5]})
        fixed.step({"a": sig_a[4:6]})               # idle slots padded
        ra = fixed.step({"a": sig_a[6:], "b": sig_b[5:]})
        solo = StreamingEngine(params, cfg, max_sessions=1)
        solo.open_session("a")
        qa = solo.step({"a": sig_a})["a"]
        np.testing.assert_allclose(np.asarray(ra["a"].summary.probs),
                                   np.asarray(qa.summary.probs),
                                   rtol=1e-5, atol=1e-6)
        assert ra["a"].steps_total == ra["b"].steps_total == T
        with pytest.raises(ValueError, match="chunk_capacity"):
            fixed.step({"a": jnp.ones((6, 1))})
        # one-graph guarantee: an all-fresh tick must present the same jit
        # pytree as later ticks (states materialized, never None)
        probe = StreamingEngine(params, cfg, max_sessions=2, chunk_capacity=5)
        sess = probe.open_session("f")
        assert probe._gather_states([sess], jnp.float32, 2) is not None

    def test_autoencoder_cobatched_equals_solo(self):
        """AE streaming: ragged co-batched == solo, bit-identical (decoder
        inherits the lengths pin, so the whole pass stays on the pinned
        graph family)."""
        cfg = ae.AutoencoderConfig(
            hidden=8, num_layers=1,
            mcd=mcd.MCDConfig(p=0.125, placement="YNYN", n_samples=2,
                              seed=1))
        params = ae.init(jax.random.key(0), cfg)
        T = 7
        sig_a = jax.random.normal(jax.random.key(8), (T, 1))
        sig_b = jax.random.normal(jax.random.key(9), (T, 1))
        eng = StreamingEngine(params, cfg, backend="pallas_seq",
                              max_sessions=2)
        eng.open_session("a")
        eng.open_session("b")
        eng.step({"a": sig_a[:3], "b": sig_b[:5]})
        ra = eng.step({"a": sig_a[3:], "b": sig_b[5:]})["a"]
        solo = StreamingEngine(params, cfg, backend="pallas_seq",
                               max_sessions=1)
        solo.open_session("a")
        solo.step({"a": sig_a[:3]})
        qa = solo.step({"a": sig_a[3:]})["a"]
        np.testing.assert_array_equal(np.asarray(ra.summary.mean),
                                      np.asarray(qa.summary.mean))
        np.testing.assert_array_equal(np.asarray(ra.summary.total),
                                      np.asarray(qa.summary.total))

    def test_bad_chunks_rejected(self):
        cfg, params = self._cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=2)
        eng.open_session("a")
        with pytest.raises(KeyError, match="unknown session"):
            eng.step({"zzz": jnp.ones((3, 1))})
        with pytest.raises(ValueError, match="t>=1"):
            eng.step({"a": jnp.ones((0, 1))})
        assert eng.step({}) == {}


class TestWindowedDecoder:
    """ISSUE 8 satellite: the windowed-decoder AE (``decode_window``).

    The encoder — and therefore the rolling bottleneck a streaming session
    carries — is untouched by the window, and the decoder replay at
    position t depends only on the bottleneck and the time-invariant
    per-row masks.  So (a) a windowed decode is bit-identical to the first
    min(T, W) positions of the full replay, and (b) chunked streaming with
    a windowed decoder stays bit-identical to unchunked, on every backend.
    """

    def _cfg_params(self, window, cell="lstm", s=2):
        cfg = ae.AutoencoderConfig(
            hidden=8, num_layers=1, cell=cell, decode_window=window,
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=s, seed=1))
        return cfg, ae.init(jax.random.key(0), cfg)

    def test_window_validation(self):
        with pytest.raises(ValueError, match="decode_window"):
            ae.AutoencoderConfig(decode_window=0)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_windowed_equals_full_prefix_bit_identical(self, backend):
        """apply() with decode_window=W == the first W positions of the
        full repeat-T replay, bit-exact (same bottleneck, same masks)."""
        W, T, B = 3, 7, 2
        cfg_w, params = self._cfg_params(W)
        cfg_full = ae.AutoencoderConfig(
            **{**dataclasses.asdict(cfg_w), "mcd": cfg_w.mcd,
               "decode_window": None})
        x = jax.random.normal(jax.random.key(2), (B, T, 1))
        rows = jnp.arange(B, dtype=jnp.uint32)
        lens = jnp.full((B,), T, jnp.int32)
        mean_w, lv_w = ae.apply(params, x, rows, cfg_w, backend=backend,
                                lengths=lens)
        mean_f, lv_f = ae.apply(params, x, rows, cfg_full, backend=backend,
                                lengths=lens)
        assert mean_w.shape == (B, W, 1)
        np.testing.assert_array_equal(np.asarray(mean_w),
                                      np.asarray(mean_f[:, :W]))
        np.testing.assert_array_equal(np.asarray(lv_w),
                                      np.asarray(lv_f[:, :W]))
        # a window past T is a no-op: full replay, full shape
        cfg_big = dataclasses.replace(cfg_w, decode_window=99)
        mean_b, _ = ae.apply(params, x, rows, cfg_big, backend=backend,
                             lengths=lens)
        np.testing.assert_array_equal(np.asarray(mean_b),
                                      np.asarray(mean_f))

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("cell", ("lstm", "gru"))
    def test_chunked_equals_unchunked_bit_identical(self, backend, cell):
        """The satellite's acceptance pin: engine streaming with a windowed
        decoder — chunked == unchunked, bit-identical, all backends.  The
        carried bottleneck is window-independent, so the final chunk's
        reconstruction matches a run that saw the prefix as one chunk."""
        W, T = 4, 9
        cfg, params = self._cfg_params(W, cell=cell)
        sig = jax.random.normal(jax.random.key(3), (T, 1))
        eng = StreamingEngine(params, cfg, backend=backend, max_sessions=1)
        eng.open_session("a")
        eng.step({"a": sig[:3]})
        eng.step({"a": sig[3:4]})                  # length-1 chunk
        got = eng.step({"a": sig[4:]})["a"]
        solo = StreamingEngine(params, cfg, backend=backend, max_sessions=1)
        solo.open_session("a")
        solo.step({"a": sig[:4]})                  # different split
        want = solo.step({"a": sig[4:]})["a"]
        # the last chunk is 5 steps but the decode window caps the
        # reconstruction at W=4 positions
        assert got.summary.mean.shape == (W, 1)
        np.testing.assert_array_equal(np.asarray(got.summary.mean),
                                      np.asarray(want.summary.mean))
        np.testing.assert_array_equal(np.asarray(got.summary.total),
                                      np.asarray(want.summary.total))
        assert got.steps_total == T

    def test_short_chunk_keeps_own_length(self):
        """Chunks shorter than the window reconstruct their full length."""
        cfg, params = self._cfg_params(window=4)
        eng = StreamingEngine(params, cfg, backend="pallas_seq",
                              max_sessions=1)
        eng.open_session("a")
        res = eng.step({"a": jnp.ones((2, 1))})["a"]
        assert res.summary.mean.shape == (2, 1)


class TestStreamingEngineGru:
    """GRU sessions through the engine: h-only carry pytrees end to end."""

    def _cfg_params(self, s=3, seed=3):
        cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, num_classes=4, cell="gru",
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=s,
                              seed=seed))
        return cfg, clf.init(jax.random.key(0), cfg)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_ragged_cobatched_equals_solo_full(self, backend):
        """Ragged co-batched chunked GRU serving == solo single-chunk
        serving, bit-identical per session."""
        cfg, params = self._cfg_params()
        T = 11
        sig_a = jax.random.normal(jax.random.key(1), (T, 1))
        sig_b = jax.random.normal(jax.random.key(2), (T, 1))
        eng = StreamingEngine(params, cfg, backend=backend, max_sessions=2)
        eng.open_session("a")
        eng.open_session("b")
        eng.step({"a": sig_a[:4], "b": sig_b[:7]})     # ragged tick
        eng.step({"a": sig_a[4:5], "b": sig_b[7:]})    # length-1 chunk for a
        ra = eng.step({"a": sig_a[5:]})["a"]           # b sits this tick out
        solo = StreamingEngine(params, cfg, backend=backend, max_sessions=1)
        solo.open_session("a")
        qa = solo.step({"a": sig_a})["a"]
        np.testing.assert_array_equal(np.asarray(ra.summary.probs),
                                      np.asarray(qa.summary.probs))
        assert ra.steps_total == qa.steps_total == T

    def test_session_state_is_h_only(self):
        cfg, params = self._cfg_params(s=2)
        eng = StreamingEngine(params, cfg, max_sessions=1)
        eng.open_session("a")
        eng.step({"a": jnp.ones((3, 1))})
        sess = eng.store.get("a")
        assert [len(layer) for layer in sess.state] == [1, 1]
        for (h,) in sess.state:
            assert h.shape == (2, cfg.hidden)

    def test_fixed_capacity_matches_dynamic(self):
        """Fixed-shape GRU ticks (idle slots padded, h-only zero states)
        serve the same results as dynamic shapes."""
        cfg, params = self._cfg_params()
        T = 9
        sig = jax.random.normal(jax.random.key(4), (T, 1))
        fixed = StreamingEngine(params, cfg, max_sessions=3, chunk_capacity=5)
        dyn = StreamingEngine(params, cfg, max_sessions=1)
        for eng in (fixed, dyn):
            eng.open_session("a")
        want = got = None
        for a, b in ((0, 4), (4, 6), (6, T)):
            got = fixed.step({"a": sig[a:b]})["a"]
            want = dyn.step({"a": sig[a:b]})["a"]
        np.testing.assert_allclose(np.asarray(got.summary.probs),
                                   np.asarray(want.summary.probs),
                                   rtol=1e-5, atol=1e-6)

    def test_autoencoder_gru_cobatched_equals_solo(self):
        cfg = ae.AutoencoderConfig(
            hidden=8, num_layers=1, cell="gru",
            mcd=mcd.MCDConfig(p=0.125, placement="YNYN", n_samples=2,
                              seed=1))
        params = ae.init(jax.random.key(0), cfg)
        T = 7
        sig_a = jax.random.normal(jax.random.key(8), (T, 1))
        sig_b = jax.random.normal(jax.random.key(9), (T, 1))
        eng = StreamingEngine(params, cfg, backend="pallas_seq",
                              max_sessions=2)
        eng.open_session("a")
        eng.open_session("b")
        eng.step({"a": sig_a[:3], "b": sig_b[:5]})
        ra = eng.step({"a": sig_a[3:], "b": sig_b[5:]})["a"]
        solo = StreamingEngine(params, cfg, backend="pallas_seq",
                               max_sessions=1)
        solo.open_session("a")
        solo.step({"a": sig_a[:3]})
        qa = solo.step({"a": sig_a[3:]})["a"]
        np.testing.assert_array_equal(np.asarray(ra.summary.mean),
                                      np.asarray(qa.summary.mean))
        np.testing.assert_array_equal(np.asarray(ra.summary.total),
                                      np.asarray(qa.summary.total))


class _EagerCarryEngine(StreamingEngine):
    """The carry path as per-part eager ops: one ``zeros`` per fresh
    session part and pad, one ``concatenate`` per layer part, one slice per
    served session part.  The oracle the compiled path must match."""

    def _gather_states(self, sessions, dtype, n_pad=0):
        if all(sess.fresh for sess in sessions) and not self._fixed:
            return None
        if self.precision is not None:
            dtype = quantize.activation_dtype(self.precision, dtype)
            c_dtype = jnp.float32
        else:
            c_dtype = dtype if self.backend == "reference" else jnp.float32
        part_dtypes = (dtype,) if self.cell == "gru" else (dtype, c_dtype)
        layers = []
        for li, hid in enumerate(self._encoder_hiddens()):
            parts = [[] for _ in part_dtypes]
            for sess in sessions:
                if sess.fresh:
                    for acc, dt in zip(parts, part_dtypes):
                        acc.append(jnp.zeros(
                            (int(sess.rows.shape[0]), hid), dt))
                else:
                    for acc, part in zip(parts, sess.state[li]):
                        acc.append(part)
            if n_pad:
                for acc, dt in zip(parts, part_dtypes):
                    acc.append(jnp.zeros((n_pad, hid), dt))
            layers.append(tuple(jnp.concatenate(acc) for acc in parts))
        return layers

    def _carry_call(self, fn, states, counts):
        assert fn.__name__ == "_split_carries"   # the gather is above
        out, off = [], 0
        for si in counts:
            sl = slice(off, off + si)
            out.append([tuple(part[sl] for part in layer)
                        for layer in states])
            off += si
        return out


def _carry_cfg(model, cell):
    p = mcd.MCDConfig(p=0.125, placement="YN", n_samples=4, seed=3)
    if model == "clf":
        cfg = clf.ClassifierConfig(hidden=8, num_layers=2, num_classes=4,
                                   cell=cell, mcd=p)
        return cfg, clf.init(jax.random.key(0), cfg)
    cfg = ae.AutoencoderConfig(hidden=8, num_layers=1, cell=cell, mcd=p)
    return cfg, ae.init(jax.random.key(0), cfg)


def _mixed_ticks(eng, sig):
    """A fresh session joins a resumed one mid-stream."""
    eng.open_session("a")
    yield {"a": sig[0][:3]}
    eng.open_session("b")
    yield {"a": sig[0][3:5], "b": sig[1][:4]}
    yield {"b": sig[1][4:6], "a": sig[0][5:9]}


def _student_ticks(eng, sig):
    """A student row co-batched with MC sessions, fresh and resumed."""
    eng.open_session("s", mode="student")
    eng.open_session("a")
    yield {"s": sig[0][:3], "a": sig[1][:2]}
    eng.open_session("b")
    yield {"a": sig[1][2:6], "b": sig[2][:5], "s": sig[0][3:4]}
    yield {"s": sig[0][4:8], "b": sig[2][5:7]}


# name: (model, cell, engine options, ticks)
_CARRY_CASES = {
    "clf-lstm-fixed": ("clf", "lstm", dict(chunk_capacity=8), _mixed_ticks),
    "clf-gru-dynamic": ("clf", "gru", {}, _mixed_ticks),
    "ae-lstm-dynamic": ("ae", "lstm", {}, _mixed_ticks),
    "ae-gru-fixed": ("ae", "gru", dict(chunk_capacity=8), _mixed_ticks),
    "clf-lstm-bf16": ("clf", "lstm", dict(precision="bf16"), _mixed_ticks),
    # every tick retires half of each session's chains: a's 4 -> 2 -> 1
    # while b joins at 4, so the later ticks pack ragged S
    "clf-lstm-early-exit": ("clf", "lstm",
                            dict(early_exit_threshold=1e9), _mixed_ticks),
    "clf-lstm-student": ("clf", "lstm", dict(chunk_capacity=8, student=True),
                         _student_ticks),
}


class TestCompiledCarryPath:
    """The tick's carry path runs as one compiled gather and one compiled
    split; concatenation and slicing are exact, so results and stored
    carries match the eager per-part path bit for bit."""

    @staticmethod
    def _serve(engine_cls, model, cell, opts, ticks):
        cfg, params = _carry_cfg(model, cell)
        opts = dict(opts)
        if opts.pop("student", False):
            opts["student"] = distill.init_student(jax.random.key(1), cfg,
                                                   params)
        eng = engine_cls(params, cfg, max_sessions=3, **opts)
        sig = [np.asarray(jax.random.normal(jax.random.key(10 + k), (9, 1)),
                          np.float32) for k in range(3)]
        trail = []
        for chunks in ticks(eng, sig):
            res = eng.step(chunks)
            trail.append((
                {sid: [np.asarray(v) for v in r.summary]
                 for sid, r in res.items()},
                {sess.sid: [[np.asarray(p) for p in layer]
                            for layer in sess.state]
                 for sess in eng.store.sessions()},
                [eng._last_served_chains[sid] for sid in chunks]))
        return trail

    @pytest.mark.parametrize("case", sorted(_CARRY_CASES))
    def test_compiled_equals_eager_bit_identical(self, case):
        model, cell, opts, ticks = _CARRY_CASES[case]
        got = self._serve(StreamingEngine, model, cell, opts, ticks)
        want = self._serve(_EagerCarryEngine, model, cell, opts, ticks)
        assert len(got) == len(want) == 3
        for (g_res, g_state, g_s), (w_res, w_state, w_s) in zip(got, want):
            assert g_s == w_s
            assert g_res.keys() == w_res.keys()
            assert g_state.keys() == w_state.keys()
            for sid in w_res:
                for g, w in zip(g_res[sid], w_res[sid]):
                    assert g.dtype == w.dtype
                    np.testing.assert_array_equal(g, w)
            for sid in w_state:
                for g_layer, w_layer in zip(g_state[sid], w_state[sid]):
                    assert len(g_layer) == len(w_layer)
                    for g, w in zip(g_layer, w_layer):
                        assert g.dtype == w.dtype
                        np.testing.assert_array_equal(g, w)
        if case == "clf-lstm-early-exit":
            # chains served per session: the later ticks pack ragged S
            assert [s for _, _, s in got] == [[4], [2, 4], [2, 1]]

    def test_fixed_shapes_compile_nothing_after_every_size(self, tmp_path):
        """After prewarm and one tick of every size, a fixed-shape stream
        compiles nothing and runs no new carry layout, whatever the chunk
        lengths, the tick's sessions or their order."""
        cfg, params = _carry_cfg("clf", "lstm")
        trail = tmp_path / "ticks.jsonl"
        eng = StreamingEngine(params, cfg, max_sessions=3, chunk_capacity=4,
                              metrics_sink=JsonlSink(str(trail)))
        for sid in "abc":
            eng.open_session(sid)
        prewarm(eng)
        x = np.ones((4, 1), np.float32)
        for k in (3, 2, 1):                      # every size, largest first
            eng.step({sid: x for sid in "abc"[:k]})
            # a new session count is a new gather and a new split
            assert eng.last_metrics.carry_layouts_new == 2
        stream = [{"b": x[:2]}, {"c": x[:1], "a": x}, {"c": x, "a": x[:3],
                                                         "b": x[:1]},
                  {"a": x[:2], "b": x}, {"a": x[:1]}]
        for chunks in stream:
            eng.step(chunks)
            m = eng.last_metrics
            assert (m.compiles, m.carry_layouts_new) == (0, 0), m
        eng.metrics_sink.close()
        counts = [json.loads(line)["carry_layouts_new"]
                  for line in trail.read_text().splitlines()]
        assert counts == [2, 2, 2] + [0] * len(stream)

    def test_cleared_state_gathers_zeros(self):
        """A session whose ``state`` is set to None before the gather reads
        as fresh: zeros in its rows, its neighbour's carry untouched (the
        contract a planted carry-unchanged fault relies on)."""
        cfg, params = _carry_cfg("clf", "lstm")
        eng = StreamingEngine(params, cfg, max_sessions=3, chunk_capacity=4)
        a, b = eng.open_session("a"), eng.open_session("b")
        x = np.ones((4, 1), np.float32)
        eng.step({"a": x, "b": x})
        kept = b.state
        a.state = None
        layers = eng._gather_states([a, b], np.float32, n_pad=4)
        assert len(layers) == cfg.num_layers
        for layer, b_layer in zip(layers, kept):
            for part, b_part in zip(layer, b_layer):
                assert part.shape == (12, cfg.hidden)
                np.testing.assert_array_equal(np.asarray(part[:4]), 0.0)
                np.testing.assert_array_equal(np.asarray(part[4:8]),
                                              np.asarray(b_part))
                assert np.any(np.asarray(b_part) != 0.0)
                np.testing.assert_array_equal(np.asarray(part[8:]), 0.0)
