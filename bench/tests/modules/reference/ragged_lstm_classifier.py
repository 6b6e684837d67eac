"""A reference for the tests alone: the classifier over chunks of varied
length.

``bench/reference/lstm_classifier.py`` steps every session over the
same number of steps; here each row of a chunk stops at its own length and
holds its state from there, as a served chunk of that length does.  The
weights, masks, cell, head and the comparison (``gaps``) are the plain
reference's own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common
from bench.reference import lstm_classifier as clf

gaps = clf.gaps


def _layer(weights, x, lens, h, c, zx, zh, p: float, matmul: str):
    """One layer over ``x [R, T, I]``; row r stops after ``lens[r]`` steps."""

    def step(carry, inp):
        x_t, t = inp
        _, h2, c2 = common.lstm_layer(weights, x_t[:, None], *carry, zx, zh,
                                      p, matmul)
        keep = (t < lens)[:, None]
        return (jnp.where(keep, h2, carry[0]),
                jnp.where(keep, c2, carry[1])), h2

    (h, c), ys = jax.lax.scan(step, (h, c), (jnp.swapaxes(x, 0, 1),
                                             jnp.arange(x.shape[1])))
    return jnp.swapaxes(ys, 0, 1), h, c


@functools.partial(jax.jit, static_argnames=("cfg", "matmul"))
def _chunk(params, masks, x, lens, states, cfg, matmul):
    stack, head = params
    inp, new_states = x, []
    for weights, (zx, zh), (h, c) in zip(stack, masks, states):
        inp, h, c = _layer(weights, inp, lens, h, c, zx, zh, cfg["p"],
                           matmul)
        new_states.append((h, c))
    logits = common.dense(head, new_states[-1][0], matmul)
    s = cfg["n_samples"]
    probs_s = jax.nn.softmax(logits.reshape(-1, s, logits.shape[-1]), -1)
    probs = probs_s.mean(axis=1)
    pred_h = clf._entropy(probs)
    return new_states, (probs, pred_h, pred_h - clf._entropy(probs_s).mean(1))


def replay(cfg: dict, weight_seed: int, mc_seed: int, chunks,
           matmul: str = "highest", carry: bool = True):
    """Summaries of every chunk of every session, in order.

    ``chunks[i]`` is session i's chunks, each ``[t]`` with its own ``t``;
    otherwise as ``lstm_classifier.replay``.
    """
    frozen = common.Static(cfg)
    s, hid, n = cfg["n_samples"], cfg["hidden"], len(chunks)
    params = clf._init(jax.random.key(weight_seed), frozen)
    dims = [cfg["input_dim"]] + [hid] * cfg["num_layers"]
    masks = common.bayesian_masks(cfg, mc_seed, common.session_rows(n, s),
                                  dims, 0)
    zeros = [(jnp.zeros((n * s, hid), jnp.float32),) * 2
             for _ in range(cfg["num_layers"])]
    width = max(len(x) for c in chunks for x in c)
    states, out = zeros, [[] for _ in range(n)]
    for j in range(max(len(c) for c in chunks)):
        states = states if carry else zeros
        x, lens = np.zeros((n, width), np.float32), np.zeros(n, np.int32)
        for i, c in enumerate(chunks):
            if len(c) > j:
                lens[i] = len(c[j])
                x[i, :lens[i]] = c[j]
        xr = np.repeat(x, s, axis=0)[..., None]
        states, summ = _chunk(params, masks, jnp.asarray(xr),
                              jnp.asarray(np.repeat(lens, s)), states, frozen,
                              matmul)
        summ = jax.device_get(summ)
        for i in np.flatnonzero(lens):
            out[i].append({f: v[i] for f, v in zip(clf.FIELDS, summ)})
    return out
