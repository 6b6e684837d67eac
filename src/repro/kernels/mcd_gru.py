"""Pallas TPU kernel: fused Bayesian GRU cell step (paper §III-A drop-in).

The paper's per-gate MCD design "can be used for other recurrent units such
as the gated recurrent unit" — this kernel is that drop-in: the same fused
datapath as :mod:`repro.kernels.mcd_lstm` with three gates instead of four
and no cell state (the GRU's whole recurrent carry is ``h``):

  Bernoulli samplers (counter PRNG, in-register)  →  DX per-gate masking of
  x and h  →  3 gate MVMs on the MXU (x- and h-side kept separate — the
  reset gate multiplies only the *recurrent* candidate matmul)  →  σ/tanh
  convex-update tail  →  h_t.

Grid: (B/bb, H/bh).  As in the LSTM step kernel each program computes all
gates for its hidden tile; ``h`` arrives twice — full-width for the
recurrent matmuls and tiled for the ``z·h`` convex update (the LSTM kernel's
``c`` tile, played by ``h`` itself here).  The update runs in fp32 and only
the stored ``h_t`` rounds to the activation dtype — the bf16-in /
fp32-accumulate policy of :func:`repro.core.cells.gru_step`.

Mask semantics are bit-identical to :func:`repro.core.mcd.gru_gate_masks`
(kind ∈ {KIND_X, KIND_H}, gate ∈ {r, z, n} = 0..2, index = row·feat_dim +
col), so this kernel, the jnp reference, and any tiling of either all
compute the same Bayesian draw.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mcd
from repro.kernels import resolve_interpret
from repro.kernels.mcd_lstm import _gate_mask


def _gru_update(x, h, h_prev, rows, keys_ref, wx_ref, wh_ref, b_ref, *,
                p_drop: float, in_dim: int, hidden: int):
    """The fused 3-gate GRU body, shared by the step and sequence kernels.

    ``h`` feeds the recurrent matmuls (must be the full hidden width);
    ``h_prev`` feeds the ``z·h`` convex update — the step kernel passes its
    *hidden tile* there, the sequence kernel passes ``h`` itself.  Returns
    h_new in fp32; numerics match :func:`repro.core.cells.gru_step` exactly
    (bit-identity across the kernels hinges on this single definition).
    """
    gx, gh = [], []
    # int32 rows: a negative id carries mcd.STUDENT_ROW_FLAG — run that row
    # deterministic (dropout off) without touching its neighbours' draw.
    det = (rows < 0)[:, None]
    scale = jnp.asarray(1.0 / (1.0 - p_drop), x.dtype) if p_drop > 0 else None
    for g in range(3):
        xg, hg = x, h
        if p_drop > 0.0:
            kx = keys_ref[0, g]     # key for (layer, KIND_X, gate g)
            kh = keys_ref[0, 3 + g]
            mx = _gate_mask(kx, rows, 0, x.shape, in_dim, p_drop)
            mh = _gate_mask(kh, rows, 0, h.shape, hidden, p_drop)
            xg = jnp.where(mx, x * scale, jnp.zeros_like(x))
            hg = jnp.where(mh, h * scale, jnp.zeros_like(h))
            xg = jnp.where(det, x, xg)
            hg = jnp.where(det, h, hg)
        # x- and h-side accumulators stay separate: the reset gate scales
        # gh[2] alone, before the candidate bias lands (cells.gru_step).
        gx.append(jnp.dot(xg, wx_ref[:, g, :],
                          preferred_element_type=jnp.float32))
        gh.append(jnp.dot(hg, wh_ref[:, g, :],
                          preferred_element_type=jnp.float32))
    r = jax.nn.sigmoid(gx[0] + gh[0] + b_ref[0, :].astype(jnp.float32))
    z = jax.nn.sigmoid(gx[1] + gh[1] + b_ref[1, :].astype(jnp.float32))
    n = jnp.tanh(gx[2] + r * gh[2] + b_ref[2, :].astype(jnp.float32))
    return (1.0 - z) * n + z * h_prev.astype(jnp.float32)


def _kernel(rows_ref, keys_ref, x_ref, h_ref, ht_ref, wx_ref, wh_ref, b_ref,
            ho_ref, *, p_drop: float, in_dim: int, hidden: int):
    rows = rows_ref[...][:, 0]
    x = x_ref[...]                  # [bb, I]
    h = h_ref[...]                  # [bb, H] — full row for the matmuls
    h_new = _gru_update(x, h, ht_ref[...], rows, keys_ref, wx_ref, wh_ref,
                        b_ref, p_drop=p_drop, in_dim=in_dim, hidden=hidden)
    ho_ref[...] = h_new.astype(ho_ref.dtype)


def gate_keys(seed, layer) -> jax.Array:
    """The 6 per-gate stream keys (x-side then h-side), shape [1, 6] uint32."""
    ks = [mcd.mask_key(seed, layer, mcd.KIND_X, g) for g in range(3)] + \
         [mcd.mask_key(seed, layer, mcd.KIND_H, g) for g in range(3)]
    return jnp.stack([jnp.asarray(k, jnp.uint32) for k in ks]).reshape(1, 6)


@functools.partial(jax.jit, static_argnames=("p_drop", "block_b", "block_h",
                                             "interpret"))
def mcd_gru_step(x: jax.Array, h: jax.Array, wx: jax.Array, wh: jax.Array,
                 b: jax.Array, rows: jax.Array, keys: jax.Array,
                 p_drop: float, *, block_b: int = 128, block_h: int = 256,
                 interpret: bool | None = None):
    """Fused Bayesian GRU step.

    x: [B, I]; h: [B, H]; wx: [I, 3, H]; wh: [H, 3, H]; b: [3, H];
    rows: [B] mask row ids; keys: [1, 6] from :func:`gate_keys`.
    Returns h_new [B, H].
    """
    B, I = x.shape
    H = h.shape[1]
    bb, bh = min(block_b, B), min(block_h, H)
    assert H % bh == 0, (H, bh)
    rows2 = rows.astype(jnp.int32).reshape(B, 1)
    pad = -B % bb        # pad to the block multiple (odd serving batches),
    if pad:              # same fallback as the LSTM kernels
        zb = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        x, h, rows2 = map(zb, (x, h, rows2))
    Bp = B + pad
    grid = (Bp // bb, H // bh)
    out = pl.pallas_call(
        functools.partial(_kernel, p_drop=p_drop, in_dim=I, hidden=H),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),      # rows
            pl.BlockSpec((1, 6), lambda i, j: (0, 0)),       # keys
            pl.BlockSpec((bb, I), lambda i, j: (i, 0)),      # x
            pl.BlockSpec((bb, H), lambda i, j: (i, 0)),      # h (full row)
            pl.BlockSpec((bb, bh), lambda i, j: (i, j)),     # h tile (z·h)
            pl.BlockSpec((I, 3, bh), lambda i, j: (0, 0, j)),  # wx
            pl.BlockSpec((H, 3, bh), lambda i, j: (0, 0, j)),  # wh
            pl.BlockSpec((3, bh), lambda i, j: (0, j)),      # bias
        ],
        out_specs=pl.BlockSpec((bb, bh), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, H), h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(rows2, keys, x, h, h, wx, wh, b)
    return out[:B] if pad else out
