"""Plain reference of the paper's Bayesian LSTM classifier (§III-C, Fig. 6b).

NL stacked LSTM layers with MC dropout on the layers the placement string
marks ``Y``, a dense head on the last layer's ``h_T``, softmax per chain,
and the chain-axis summary: mean probabilities, predictive entropy
``H[E_s p_s]``, expected entropy ``E_s H[p_s]`` and their difference, the
mutual information.  A session's ``(h, c)`` carries from chunk to chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common

FIELDS = ("probs", "predictive_entropy", "mutual_information")

#: How far a class probability or the mutual information (nats) may lie
#: from the reference's before the answer is wrong: sound runs on the chip
#: read at most 1.1e-5 and 1.5e-6 (PERF.md, section 2).
ANSWER_TOL = 1e-3
#: The least move of a chunk's probabilities by the carry for the chunk to
#: count in ``carry_gap_ratio``: where the carry moves them less, sound
#: runs' float32 rounding is of the same order as the move.
CARRY_FLOOR = 1e-6


def _entropy(p):
    return -jnp.sum(p * jnp.log(jnp.clip(p, 1e-12, 1.0)), axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _init(key, cfg):
    k_enc, k_head = jax.random.split(key)
    hid = cfg["hidden"]
    return (common.init_lstm_stack(k_enc, cfg["input_dim"],
                                   (hid,) * cfg["num_layers"]),
            common.init_dense(k_head, hid, cfg["num_classes"]))


@functools.partial(jax.jit, static_argnames=("cfg", "matmul"))
def _chunk(params, masks, x, states, valid, cfg, matmul):
    stack, head = params
    inp, new_states = x, []
    for weights, (zx, zh), (h, c) in zip(stack, masks, states):
        inp, h2, c2 = common.lstm_layer(weights, inp, h, c, zx, zh, cfg["p"],
                                        matmul)
        keep = valid[:, None]
        new_states.append((jnp.where(keep, h2, h), jnp.where(keep, c2, c)))
    logits = common.dense(head, new_states[-1][0], matmul)
    s = cfg["n_samples"]
    probs_s = jax.nn.softmax(logits.reshape(-1, s, logits.shape[-1]), -1)
    probs = probs_s.mean(axis=1)
    pred_h = _entropy(probs)
    return new_states, (probs, pred_h, pred_h - _entropy(probs_s).mean(1))


def replay(cfg: dict, weight_seed: int, mc_seed: int, chunks,
           matmul: str = "highest", carry: bool = True):
    """Summaries of every chunk of every session, in order.

    ``chunks[i]`` is session i's beats ``[n_i, T]``; returns
    ``out[i][j] = {field: array}``.  ``matmul`` is the precision of every
    matmul (``common.mdot``); without ``carry`` every chunk starts from the
    zero state.
    """
    frozen = common.Static(cfg)
    s, hid, n = cfg["n_samples"], cfg["hidden"], len(chunks)
    rows = common.session_rows(n, s)
    params = _init(jax.random.key(weight_seed), frozen)
    dims = [cfg["input_dim"]] + [hid] * cfg["num_layers"]
    masks = common.bayesian_masks(cfg, mc_seed, rows, dims, 0)
    zeros = [(jnp.zeros((n * s, hid), jnp.float32),) * 2
             for _ in range(cfg["num_layers"])]
    states, out = zeros, [[] for _ in range(n)]
    for j in range(max(len(c) for c in chunks)):
        states = states if carry else zeros
        has = np.array([len(c) > j for c in chunks])
        x = np.zeros((n, chunks[0].shape[1]), np.float32)
        for i in np.flatnonzero(has):
            x[i] = chunks[i][j]
        xr = np.repeat(x, s, axis=0)[..., None]
        states, summ = _chunk(params, masks, jnp.asarray(xr), states,
                              jnp.asarray(np.repeat(has, s)), frozen, matmul)
        summ = jax.device_get(summ)
        for i in np.flatnonzero(has):
            out[i].append({f: v[i] for f, v in zip(FIELDS, summ)})
    return out


def gaps(got, ref, reset) -> dict:
    """The numbers that decide ``correct``, from served and reference summaries.

    ``reset`` is the reference replayed without the carry.  Per chunk, the
    probability gap is the largest absolute difference of a mean class
    probability.  Returned:

    * ``probs_gap_median``: the median probability gap over all chunks;
    * ``wrong_answers``: chunks whose answer says another thing than the
      reference's: a class probability or the mutual information off by
      more than ``ANSWER_TOL``, or another class on top where the
      reference's two most likely classes lie ``ANSWER_TOL`` apart or more;
    * ``carry_gap_ratio``: the median, over every chunk after a session's
      first where the carry moves the reference's probabilities by
      ``CARRY_FLOOR`` or more, of its probability gap over that move: near
      0 for a served carry, 1 for none (0 where no chunk qualifies);
    * ``probs_gap`` and ``mi_gap_median``, the widest probability gap and
      the median gap of the mutual information, read but not compared.
    """
    names = ("probs_gap_median", "wrong_answers", "carry_gap_ratio",
             "probs_gap", "mi_gap_median")
    probs, mi, ratio, wrong = [], [], [], 0
    for g_s, r_s, z_s in zip(got, ref, reset):
        if len(g_s) != len(r_s):
            return dict.fromkeys(names, float("inf"))
        for j, (g, r, z) in enumerate(zip(g_s, r_s, z_s)):
            g_p = np.asarray(g["probs"], np.float64)
            r_p = np.asarray(r["probs"], np.float64)
            probs.append(float(np.abs(g_p - r_p).max()))
            mi.append(abs(float(g["mutual_information"])
                          - float(r["mutual_information"])))
            top2 = np.sort(r_p)[-2:]
            wrong += (probs[-1] > ANSWER_TOL or mi[-1] > ANSWER_TOL
                      or (top2[1] - top2[0] >= ANSWER_TOL
                          and g_p.argmax() != r_p.argmax()))
            carry = float(np.abs(np.asarray(z["probs"]) - r_p).max())
            if j and carry >= CARRY_FLOOR:
                ratio.append(probs[-1] / carry)
    return dict(zip(names, (
        float(np.median(probs)), float(wrong),
        float(np.median(ratio)) if ratio else 0.0, max(probs),
        float(np.median(mi)))))
