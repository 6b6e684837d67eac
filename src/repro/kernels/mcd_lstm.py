"""Pallas TPU kernel: fused Bayesian LSTM cell step — the paper's Fig. 2.

One kernel = the whole per-timestep datapath of the paper's accelerator:
  Bernoulli samplers (counter PRNG in VMEM)  →  DX per-gate masking of x and
  h  →  4 gate MVMs on the MXU  →  σ/tanh + elementwise tail  →  (h_t, c_t).

Grid: (B/bb, H/bh).  Each program instance computes all four gates for its
hidden tile so the elementwise tail fuses locally (the paper's "LSTM tail"
unit).  Weights are laid out [I, 4, H] / [H, 4, H] so a tile loads the
contiguous gate stack for its hidden columns.  The cell state is carried in
fp32 (paper: c in 32-bit, everything else 16-bit).

Mask semantics are bit-identical to :func:`repro.core.mcd.lstm_gate_masks`
(kind ∈ {KIND_X, KIND_H}, gate ∈ {i,f,g,o}, index = row·feat_dim + col), so
this kernel, the jnp reference, and any sharded layout of either all compute
the same Bayesian draw.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mcd, prng
from repro.kernels import resolve_interpret


def _gate_mask(key, rows, cols0, shape, feat_dim: int, p_drop: float):
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1) + jnp.uint32(cols0)
    idx = rows[:, None].astype(jnp.uint32) * jnp.uint32(feat_dim) + cols
    bits = prng._mix32(jnp.asarray(key, jnp.uint32) ^ prng._mix32(idx))
    return bits >= prng.bernoulli_keep_threshold(p_drop)


def _kernel(rows_ref, keys_ref, x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref,
            ho_ref, co_ref, *, p_drop: float, in_dim: int, hidden: int):
    rows = rows_ref[...][:, 0]
    x = x_ref[...]                  # [bb, I]
    h = h_ref[...]                  # [bb, H]
    # Rows are int32 in-kernel, so the student flag (mcd.STUDENT_ROW_FLAG,
    # the uint32 high bit) is simply the sign bit: negative row = run this
    # row deterministic (dropout off), leaving every other row's draw alone.
    det = (rows < 0)[:, None]
    gates = []
    scale = jnp.asarray(1.0 / (1.0 - p_drop), x.dtype) if p_drop > 0 else None
    for g in range(4):
        xg, hg = x, h
        if p_drop > 0.0:
            kx = keys_ref[0, g]     # key for (layer, KIND_X, gate g)
            kh = keys_ref[0, 4 + g]
            mx = _gate_mask(kx, rows, 0, x.shape, in_dim, p_drop)
            mh = _gate_mask(kh, rows, 0, h.shape, hidden, p_drop)
            xg = jnp.where(mx, x * scale, jnp.zeros_like(x))
            hg = jnp.where(mh, h * scale, jnp.zeros_like(h))
            xg = jnp.where(det, x, xg)
            hg = jnp.where(det, h, hg)
        acc = jnp.dot(xg, wx_ref[:, g, :], preferred_element_type=jnp.float32)
        acc += jnp.dot(hg, wh_ref[:, g, :], preferred_element_type=jnp.float32)
        gates.append(acc + b_ref[g, :].astype(jnp.float32))
    i = jax.nn.sigmoid(gates[0])
    f = jax.nn.sigmoid(gates[1])
    g_ = jnp.tanh(gates[2])
    o = jax.nn.sigmoid(gates[3])
    c_new = f * c_ref[...].astype(jnp.float32) + i * g_
    co_ref[...] = c_new.astype(co_ref.dtype)
    ho_ref[...] = (o * jnp.tanh(c_new)).astype(ho_ref.dtype)


def gate_keys(seed, layer) -> jax.Array:
    """The 8 per-gate stream keys (x-side then h-side), shape [1, 8] uint32."""
    ks = [mcd.mask_key(seed, layer, mcd.KIND_X, g) for g in range(4)] + \
         [mcd.mask_key(seed, layer, mcd.KIND_H, g) for g in range(4)]
    return jnp.stack([jnp.asarray(k, jnp.uint32) for k in ks]).reshape(1, 8)


@functools.partial(jax.jit, static_argnames=("p_drop", "block_b", "block_h",
                                             "interpret"))
def mcd_lstm_step(x: jax.Array, h: jax.Array, c: jax.Array, wx: jax.Array,
                  wh: jax.Array, b: jax.Array, rows: jax.Array,
                  keys: jax.Array, p_drop: float, *, block_b: int = 128,
                  block_h: int = 256, interpret: bool | None = None):
    """Fused Bayesian LSTM step.

    x: [B, I]; h, c: [B, H]; wx: [I, 4, H]; wh: [H, 4, H]; b: [4, H];
    rows: [B] mask row ids; keys: [1, 8] from :func:`gate_keys`.
    Returns (h_new [B, H], c_new [B, H] fp32).
    """
    B, I = x.shape
    H = h.shape[1]
    bb, bh = min(block_b, B), min(block_h, H)
    assert H % bh == 0, (H, bh)
    rows2 = rows.astype(jnp.int32).reshape(B, 1)
    pad = -B % bb        # pad to the block multiple (odd serving batches),
    if pad:              # same fallback as the sequence kernel
        zb = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        x, h, c, rows2 = map(zb, (x, h, c, rows2))
    Bp = B + pad
    grid = (Bp // bb, H // bh)
    out = pl.pallas_call(
        functools.partial(_kernel, p_drop=p_drop, in_dim=I, hidden=H),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),      # rows
            pl.BlockSpec((1, 8), lambda i, j: (0, 0)),       # keys
            pl.BlockSpec((bb, I), lambda i, j: (i, 0)),      # x
            pl.BlockSpec((bb, H), lambda i, j: (i, 0)),      # h (full row)
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),     # c tile
            pl.BlockSpec((I, 4, bh), lambda i, j: (0, 0, j)),  # wx
            pl.BlockSpec((H, 4, bh), lambda i, j: (0, 0, j)),  # wh
            pl.BlockSpec((4, bh), lambda i, j: (0, j)),      # bias
        ],
        out_specs=[
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, H), h.dtype),
            jax.ShapeDtypeStruct((Bp, H), c.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(rows2, keys, x, h, c, wx, wh, b)
    if pad:
        out = [o[:B] for o in out]
    return out
