"""Multi-device data plane: sharded == unsharded, bit for bit.

The ISSUE 5 acceptance invariants live here:

* ``run_stack(mesh=...)`` output is **bit-identical** to the unsharded
  lengths-enabled reference at device counts 1, 2 and 8, for both cells,
  under both strategies (shard_map data partition and the GSPMD wide-H
  fallback with batch rows over ``data``) — masks key off global
  ``(seed, rows)`` coordinates, so no device ever draws different bits;
  with H split over ``model`` as well, the GSPMD fallback agrees within
  a few ulp;
* chunked == unchunked stays bit-identical *through* the mesh (carried
  state crosses shard boundaries losslessly);
* a mesh-placed ``StreamingEngine`` serves bit-identically to an
  unsharded one, and a snapshot taken on an N-device engine restores onto
  a 1-device engine (and vice versa) — host-portability of the durable
  state.

Device counts above the host's are skipped; CI runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the 2- and
8-way cases are exercised (single-device runs still pin the mesh=1 path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import classifier as clf, mcd, rnn
from repro.launch import rnn_shardings as rs
from repro.launch.mesh import make_data_mesh
from repro.serve import StreamingEngine

DEVICE_COUNTS = (1, 2, 8)
CELLS = ("lstm", "gru")


def _mesh_or_skip(n_data: int, model: int = 1):
    if n_data * model > len(jax.devices()):
        pytest.skip(f"needs {n_data * model} devices, host has "
                    f"{len(jax.devices())} (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    return make_data_mesh(n_data, model=model)


def _stack(cell, B=7, T=5, H=8, NL=3, seed=0, dtype=jnp.float32):
    cfg = mcd.MCDConfig(p=0.125, placement="YNY", n_samples=2, seed=seed)
    params = rnn.init_stack(jax.random.key(0), 1, (H,) * NL, dtype, cell=cell)
    rows = jnp.arange(B, dtype=jnp.uint32)
    x = jax.random.normal(jax.random.key(1), (B, T, 1), dtype)
    lengths = jnp.asarray([(i % T) + 1 for i in range(B)], jnp.int32)
    return cfg, params, rows, x, lengths


def _assert_tree_equal(got, want):
    for la, lb in zip(got, want):
        for a, b in zip(la, lb):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestShardedStack:
    @pytest.mark.parametrize("cell", CELLS)
    @pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
    def test_data_strategy_bit_identical(self, cell, n_dev):
        mesh = _mesh_or_skip(n_dev)
        cfg, params, rows, x, lengths = _stack(cell)
        masks = rnn.stack_mask_plan(cfg, 3)
        ref_o, ref_s = rnn.run_stack(params, x, masks, cfg.p,
                                     backend="pallas_seq", rows=rows,
                                     seed=cfg.seed, lengths=lengths,
                                     return_all_states=True, cell=cell)
        out, states = rnn.run_stack(params, x, masks, cfg.p,
                                    backend="pallas_seq", rows=rows,
                                    seed=cfg.seed, lengths=lengths,
                                    return_all_states=True, cell=cell,
                                    mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_o))
        _assert_tree_equal(states, ref_s)

    @pytest.mark.parametrize("cell", CELLS)
    @pytest.mark.parametrize("n_dev", DEVICE_COUNTS)
    def test_gspmd_strategy_bit_identical(self, cell, n_dev):
        """The wide-H fallback (reference scan under GSPMD) with batch rows
        over `data` draws the same bits and computes the same numbers as
        the Pallas launch — the lengths-pinned graph family is backend- and
        shard-invariant."""
        self._check_gspmd(cell, n_dev, model=1, atol=None)

    @pytest.mark.parametrize("cell", CELLS)
    @pytest.mark.parametrize("n_dev", (1, 2))
    def test_gspmd_strategy_h_split_matches(self, cell, n_dev):
        """With H split over `model` too, no reduction is split (XLA
        all-gathers h), but each device runs narrower elementwise and dot
        fusions, which the CPU backend may round differently: the claim
        is agreement within a few ulp, not bit-identity."""
        self._check_gspmd(cell, n_dev, model=2, atol=1e-6)

    @staticmethod
    def _check_gspmd(cell, n_dev, *, model, atol):
        mesh = _mesh_or_skip(n_dev, model)
        cfg, params, rows, x, lengths = _stack(cell)
        masks = rnn.stack_mask_plan(cfg, 3)
        ref_o, _ = rnn.run_stack(params, x, masks, cfg.p,
                                 backend="pallas_seq", rows=rows,
                                 seed=cfg.seed, lengths=lengths,
                                 return_all_states=True, cell=cell)
        out, _ = rnn.run_stack(params, x, masks, cfg.p, backend="pallas_seq",
                               rows=rows, seed=cfg.seed, lengths=lengths,
                               return_all_states=True, cell=cell, mesh=mesh,
                               policy=rs.StackShardingPolicy(strategy="gspmd"))
        if atol is None:
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_o))
        else:
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                                       rtol=0, atol=atol)

    @pytest.mark.parametrize("cell", CELLS)
    def test_chunked_equals_unchunked_through_mesh(self, cell):
        """Carried state crosses chunk boundaries losslessly on a mesh:
        chunk 1 sharded → carry → chunk 2 sharded == one unsharded pass."""
        n_dev = max(c for c in DEVICE_COUNTS if c <= len(jax.devices()))
        mesh = make_data_mesh(n_dev)
        cfg, params, rows, x, _ = _stack(cell, T=6)
        T = x.shape[1]
        full = jnp.full((x.shape[0],), T, jnp.int32)
        masks = rnn.stack_mask_plan(cfg, 3)
        kw = dict(p=cfg.p, backend="pallas_seq", rows=rows, seed=cfg.seed,
                  return_all_states=True, cell=cell)
        _, want = rnn.run_stack(params, x, masks, lengths=full, **kw)
        cut = 3
        part = jnp.full((x.shape[0],), cut, jnp.int32)
        _, s1 = rnn.run_stack(params, x[:, :cut], masks, lengths=part,
                              mesh=mesh, **kw)
        _, got = rnn.run_stack(params, x[:, cut:], masks,
                               lengths=full - cut, initial_state=s1,
                               mesh=mesh, **kw)
        _assert_tree_equal(got, want)

    def test_reference_backend_routes_to_gspmd(self):
        mesh = _mesh_or_skip(1)
        cfg, params, rows, x, lengths = _stack("lstm")
        masks = rnn.sample_stack_masks(cfg, rows, 1, (8,) * 3)
        ref_o, _ = rnn.run_stack(params, x, masks, cfg.p,
                                 backend="reference", rows=rows,
                                 lengths=lengths, return_all_states=True)
        out, _ = rnn.run_stack(params, x, masks, cfg.p, backend="reference",
                               rows=rows, lengths=lengths,
                               return_all_states=True, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_o))

    def test_host_numpy_masks_accepted(self):
        """Regression: numpy mask values (not jax.Arrays) used to land in
        the static plan that keys the compiled-callable cache →
        'unhashable type: numpy.ndarray'.  They must behave like the
        unsharded path: arrays are arrays, wherever they were made."""
        mesh = _mesh_or_skip(1)
        cfg, params, rows, x, lengths = _stack("lstm")
        masks = [tuple(None if m is None else np.asarray(m) for m in pair)
                 for pair in rnn.sample_stack_masks(cfg, rows, 1, (8,) * 3)]
        ref_o, _ = rnn.run_stack(params, x, masks, cfg.p,
                                 backend="reference", rows=rows,
                                 lengths=lengths, return_all_states=True)
        out, _ = rnn.run_stack(params, x, masks, cfg.p, backend="reference",
                               rows=rows, lengths=lengths,
                               return_all_states=True, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_o))

    def test_mesh_requires_rows(self):
        mesh = _mesh_or_skip(1)
        cfg, params, _, x, lengths = _stack("lstm")
        with pytest.raises(ValueError, match="rows"):
            rnn.run_stack(params, x, rnn.stack_mask_plan(cfg, 3), cfg.p,
                          backend="pallas_seq", lengths=lengths, mesh=mesh)


class TestPolicy:
    def test_resolve_strategy(self):
        mesh = _mesh_or_skip(1, 1)
        po = rs.DEFAULT_POLICY
        assert rs.resolve_strategy(mesh, po, "reference", [8]) == "gspmd"
        assert rs.resolve_strategy(mesh, po, "pallas_seq", [8]) == "data"
        # wide H falls back to gspmd only when a model axis exists to use
        assert rs.resolve_strategy(mesh, po, "pallas_seq", [4096]) == "data"
        if len(jax.devices()) >= 2:
            mesh2 = make_data_mesh(1, model=2)
            assert rs.resolve_strategy(mesh2, po, "pallas_seq",
                                       [4096]) == "gspmd"
            assert rs.resolve_strategy(mesh2, po, "pallas_seq",
                                       [8]) == "data"
        forced = rs.StackShardingPolicy(strategy="gspmd")
        assert rs.resolve_strategy(mesh, forced, "pallas_seq", [8]) == "gspmd"
        with pytest.raises(ValueError, match="strategy"):
            rs.StackShardingPolicy(strategy="banana")

    def test_param_specs_shard_h_out_dim_only(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices for a model axis")
        mesh = make_data_mesh(1, model=2)
        params = rnn.init_stack(jax.random.key(0), 1, (8, 8))
        specs = rs.stack_param_specs(params, mesh, strategy="gspmd")
        for sp in specs:
            assert sp.wx[-1] == "model" and sp.wh[-1] == "model"
            assert sp.wh[1] is None          # contraction dim never sharded
        # indivisible H replicates instead of erroring
        odd = rnn.init_stack(jax.random.key(0), 1, (7,))
        (sp,) = rs.stack_param_specs(odd, mesh, strategy="gspmd")
        assert sp.wh[-1] is None
        # the data strategy replicates weights entirely
        for sp in rs.stack_param_specs(params, mesh, strategy="data"):
            assert all(ax is None for ax in sp.wh)

    def test_shard_pad_floor(self):
        assert rs._shard_pad(7, 1) == 0       # 1 device = exact unsharded run
        assert rs._shard_pad(7, 2) == 1       # even split
        assert rs._shard_pad(8, 8) == 8       # 2-row floor per shard
        assert rs._shard_pad(16, 8) == 0


class TestShardedEngine:
    def _engine(self, cell, mesh, s=2, max_sessions=3):
        cfg = clf.ClassifierConfig(
            hidden=8, num_layers=2, cell=cell,
            mcd=mcd.MCDConfig(p=0.125, placement="YN", n_samples=s, seed=3))
        params = clf.init(jax.random.key(0), cfg)
        return StreamingEngine(params, cfg, backend="pallas_seq",
                               max_sessions=max_sessions, mesh=mesh)

    @pytest.mark.parametrize("cell", CELLS)
    def test_mesh_engine_serves_bit_identically(self, cell):
        n_dev = max(c for c in DEVICE_COUNTS if c <= len(jax.devices()))
        plain = self._engine(cell, None)
        meshy = self._engine(cell, make_data_mesh(n_dev))
        sigs = {f"s{k}": jax.random.normal(jax.random.key(k), (9, 1))
                for k in range(3)}
        for eng in (plain, meshy):
            for sid in sigs:
                eng.open_session(sid)
        # ragged ticks: different chunk lengths per session per tick
        for lens in ((9, 4, 7), (3, 9, 1)):
            want = plain.step({sid: sig[:n] for (sid, sig), n
                               in zip(sigs.items(), lens)})
            got = meshy.step({sid: sig[:n] for (sid, sig), n
                              in zip(sigs.items(), lens)})
            for sid in want:
                np.testing.assert_array_equal(
                    np.asarray(want[sid].summary.probs),
                    np.asarray(got[sid].summary.probs))
        assert meshy.last_metrics.shards == n_dev
        assert plain.last_metrics.shards == 1

    def test_snapshot_is_mesh_portable(self, tmp_path):
        """Snapshot on an N-device engine, restore on a 1-device engine:
        the continuation is bit-identical to the uninterrupted unsharded
        run (and the N-dev continuation matches too) — durable state
        carries nothing device-shaped."""
        n_dev = max(c for c in DEVICE_COUNTS if c <= len(jax.devices()))
        sig = jax.random.normal(jax.random.key(9), (12, 1))
        # uninterrupted, unsharded ground truth
        base = self._engine("lstm", None)
        base.open_session("p")
        base.step({"p": sig[:5]})
        want = base.step({"p": sig[5:]})["p"]
        # sharded engine, killed mid-stream
        meshy = self._engine("lstm", make_data_mesh(n_dev))
        meshy.open_session("p")
        meshy.step({"p": sig[:5]})
        meshy.snapshot(str(tmp_path))
        # restored onto a single device (mesh=None)
        fresh = self._engine("lstm", None)
        fresh.restore(str(tmp_path))
        got = fresh.step({"p": sig[5:]})["p"]
        np.testing.assert_array_equal(np.asarray(want.summary.probs),
                                      np.asarray(got.summary.probs))
        assert got.steps_total == want.steps_total
        # and back onto a mesh: 1-dev snapshot → N-dev engine
        base2 = self._engine("lstm", None)
        base2.open_session("p")
        base2.step({"p": sig[:5]})
        snap2 = tmp_path / "snap2"
        base2.snapshot(str(snap2))
        meshy2 = self._engine("lstm", make_data_mesh(n_dev))
        meshy2.restore(str(snap2))
        got2 = meshy2.step({"p": sig[5:]})["p"]
        np.testing.assert_array_equal(np.asarray(want.summary.probs),
                                      np.asarray(got2.summary.probs))

    def test_slot_padding_keeps_whole_sessions_per_shard(self):
        """max_sessions that doesn't divide the shard count pads slots up:
        batch_rows is a multiple of shards × S and results are unchanged."""
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        meshy = self._engine("lstm", make_data_mesh(2), s=3, max_sessions=3)
        plain = self._engine("lstm", None, s=3, max_sessions=3)
        sig = jax.random.normal(jax.random.key(2), (6, 1))
        for eng in (meshy, plain):
            eng.open_session("a")
            eng.step({"a": sig})
        m = meshy.last_metrics
        assert m.batch_rows % (2 * 3) == 0
        np.testing.assert_array_equal(
            np.asarray(meshy.store.get("a").state[0][0]),
            np.asarray(plain.store.get("a").state[0][0]))
