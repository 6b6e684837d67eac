"""Trainer + checkpoint fault-tolerance behaviour."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint
from repro.train import optimizer, trainer


def _toy_problem(n=640):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    Y = (X @ np.array([[1.0], [2.0], [-0.5]]) + 0.3).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(Y)


def _loss(params, batch, step):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2), {}


def _batches(X, Y, bs=64):
    for i in range(0, len(X), bs):
        yield X[i:i + bs], Y[i:i + bs]


def _params():
    return {"w": jnp.zeros((3, 1)), "b": jnp.zeros((1,))}


class TestTrainer:
    def test_loss_decreases(self):
        X, Y = _toy_problem()
        cfg = trainer.TrainConfig(adamw=optimizer.AdamWConfig(lr=0.05),
                                  log_every=0)
        tr = trainer.Trainer(_loss, _params(), cfg)
        hist = tr.run(_batches(X, Y), 10)
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_microbatch_equals_full(self):
        """Grad accumulation over microbatches == one big batch (same update)."""
        X, Y = _toy_problem(128)
        batch = (X, Y)
        p0 = _params()
        s0 = optimizer.init(p0)
        err = jax.tree.map(lambda p: jnp.zeros((), jnp.float32), p0)
        f1 = trainer.make_train_step(_loss, trainer.TrainConfig(microbatches=1))
        f4 = trainer.make_train_step(_loss, trainer.TrainConfig(microbatches=4))
        p1, *_ = f1(p0, s0, err, batch, jnp.int32(0))
        p4, *_ = f4(p0, s0, err, batch, jnp.int32(0))
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p4)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_compressed_grads_converge(self, mode):
        """Error-feedback compression still reaches a good solution."""
        X, Y = _toy_problem()
        cfg = trainer.TrainConfig(adamw=optimizer.AdamWConfig(lr=0.05),
                                  grad_compression=mode, log_every=0)
        tr = trainer.Trainer(_loss, _params(), cfg)
        hist = tr.run((b for _ in range(6) for b in _batches(X, Y)), 50)
        assert hist[-1]["loss"] < 0.1 * hist[0]["loss"]

    def test_clip_norm(self):
        g = {"a": jnp.full((10,), 100.0)}
        clipped, norm = optimizer.clip_by_global_norm(g, 3.0)
        assert float(norm) > 3.0
        assert abs(float(optimizer.global_norm(clipped)) - 3.0) < 1e-4

    def test_straggler_watchdog(self):
        cfg = trainer.TrainConfig(straggler_factor=2.0, log_every=0)
        tr = trainer.Trainer(_loss, _params(), cfg)
        tr.step_times = [0.1] * 20
        tr._watchdog(0.5)
        assert tr.straggler_events


class TestCheckpoint:
    def test_resume_continues(self, tmp_path):
        X, Y = _toy_problem()
        d = str(tmp_path / "ck")
        cfg = trainer.TrainConfig(adamw=optimizer.AdamWConfig(lr=0.05),
                                  ckpt_dir=d, ckpt_every=5, log_every=0)
        tr1 = trainer.Trainer(_loss, _params(), cfg)
        tr1.run(_batches(X, Y), 10)
        assert checkpoint.latest_step(d) == 10
        # simulate crash + restart: a fresh Trainer resumes at step 10
        tr2 = trainer.Trainer(_loss, _params(), cfg)
        assert tr2.step == 10
        for a, b in zip(jax.tree_util.tree_leaves(tr1.params),
                        jax.tree_util.tree_leaves(tr2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_corruption_detected_and_skipped(self, tmp_path):
        d = str(tmp_path / "ck")
        tree = {"w": jnp.arange(8.0)}
        checkpoint.save(d, 1, tree)
        checkpoint.save(d, 2, jax.tree.map(lambda x: x * 2, tree))
        # corrupt the newest checkpoint
        victim = os.path.join(d, "step-0000000002", "w.npy")
        with open(victim, "r+b") as f:
            f.seek(-1, 2)
            f.write(b"\x00")
        with pytest.raises(IOError):
            checkpoint.restore(d, 2, tree)
        step, restored = checkpoint.resume_or_none(d, tree)
        assert step == 1                       # fell back to the older one
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(8.0))

    def test_atomicity_no_partial_dir(self, tmp_path):
        d = str(tmp_path / "ck")
        checkpoint.save(d, 3, {"x": jnp.ones(4)})
        assert not [f for f in os.listdir(d) if f.startswith(".tmp")]

    def test_elastic_reshard_restore(self, tmp_path):
        """Restore onto explicit shardings (the elastic-rescale path)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        d = str(tmp_path / "ck")
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        checkpoint.save(d, 1, tree)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        shardings = {"w": NamedSharding(mesh, P("data", None))}
        restored = checkpoint.restore(d, 1, tree, shardings=shardings)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding == shardings["w"]

    def test_keep_last(self, tmp_path):
        d = str(tmp_path / "ck")
        for s in (1, 2, 3, 4):
            checkpoint.save(d, s, {"x": jnp.ones(2) * s})
        checkpoint.keep_last(d, 2)
        steps = sorted(int(f.split("-")[1]) for f in os.listdir(d))
        assert steps == [3, 4]

    def test_meta_rides_the_manifest(self, tmp_path):
        """save(meta=) commits JSON alongside the arrays atomically; it comes
        back via load_meta and never perturbs the array restore."""
        d = str(tmp_path / "ck")
        tree = {"x": jnp.ones(3)}
        checkpoint.save(d, 1, tree, meta={"cursor": 7, "sids": ["a", "b"]})
        assert checkpoint.load_meta(d, 1) == {"cursor": 7,
                                              "sids": ["a", "b"]}
        np.testing.assert_array_equal(
            np.asarray(checkpoint.restore(d, 1, tree)["x"]), np.ones(3))
        checkpoint.save(d, 2, tree)                  # meta stays optional
        assert checkpoint.load_meta(d, 2) is None

    def test_meta_must_be_json(self, tmp_path):
        with pytest.raises(TypeError):
            checkpoint.save(str(tmp_path / "ck"), 1, {"x": jnp.ones(2)},
                            meta={"bad": jnp.ones(2)})

    def test_partial_restore_subset(self, tmp_path):
        """A like-tree naming a subset of the saved leaves restores just
        that subset; a leaf the manifest doesn't know stays an error."""
        d = str(tmp_path / "ck")
        tree = {"a": {"w": jnp.arange(4.0)}, "b": {"w": jnp.arange(2.0)}}
        checkpoint.save(d, 1, tree)
        sub = checkpoint.restore(d, 1, {"a": {"w": 0}}, partial=True)
        np.testing.assert_array_equal(np.asarray(sub["a"]["w"]),
                                      np.arange(4.0))
        with pytest.raises(KeyError, match="not in checkpoint"):
            checkpoint.restore(d, 1, {"zz": {"w": 0}}, partial=True)
        # without partial=True a truncated like-tree is a caller bug
        with pytest.raises(ValueError, match="partial=True"):
            checkpoint.restore(d, 1, {"a": {"w": 0}})

    def test_partial_restore_refuses_deduped_names(self, tmp_path):
        """'a b' and 'a_b' sanitize to the same leaf name; the positional
        __k disambiguation is full-tree-order dependent, so a partial
        restore must refuse rather than silently return a sibling's
        array."""
        d = str(tmp_path / "ck")
        checkpoint.save(d, 1, {"a b": jnp.zeros(2), "a_b": jnp.ones(2)})
        with pytest.raises(ValueError, match="disambiguated"):
            checkpoint.restore(d, 1, {"a_b": 0}, partial=True)
        full = checkpoint.restore(d, 1, {"a b": 0, "a_b": 0})
        np.testing.assert_array_equal(np.asarray(full["a_b"]), np.ones(2))
