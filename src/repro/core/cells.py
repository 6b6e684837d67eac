"""LSTM / GRU cells with the paper's per-gate MCD mask views.

Paper §II-A decouples the input and hidden state per gate
(x^i, x^f, x^g, x^o = x;  h^i, ... = h) precisely so that MCD can mask each
view independently.  We keep that decoupling: weights are stored as
``[4, in, hidden]`` stacks (gate axis first) and the masked views are applied
per-gate before the gate matmuls.

On the FPGA each gate had its own MVM unit (Fig. 2); here each gate is its
own ``[B,I] × [I,H]`` matmul too, in the form the kernels use, so the jnp
path and the kernels accumulate in the same order.  A Pallas-fused version
of the full step (masks + matmuls + nonlinearities + cell update) lives in
``repro.kernels.mcd_lstm``; this module is the composable/jnp path and the
numerical ground truth for it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import mcd


class LSTMParams(NamedTuple):
    wx: jax.Array  # [4, in_dim, hidden]
    wh: jax.Array  # [4, hidden, hidden]
    b: jax.Array   # [4, hidden]


def init_lstm(key: jax.Array, in_dim: int, hidden: int,
              dtype=jnp.float32) -> LSTMParams:
    kx, kh = jax.random.split(key)
    sx = (6.0 / (in_dim + hidden)) ** 0.5
    sh = (6.0 / (2 * hidden)) ** 0.5
    wx = jax.random.uniform(kx, (4, in_dim, hidden), dtype, -sx, sx)
    wh = jax.random.uniform(kh, (4, hidden, hidden), dtype, -sh, sh)
    b = jnp.zeros((4, hidden), dtype)
    # forget-gate bias 1.0 (standard recurrent practice)
    b = b.at[1].set(jnp.ones((hidden,), dtype))
    return LSTMParams(wx, wh, b)


def freeze_rows(t, lengths, h_new, c_new, h_old, c_old):
    """Per-row streaming freeze: keep the old carry once ``t >= lengths``.

    Single source for the ragged-chunk select used by the reference scan,
    the step-kernel scan and the sequence-kernel oracle.  The exact
    formulation (one ``<`` compare, two selects on the *new* values) is part
    of the bit-identity contract across backends — see docs/kernels.md
    "numerics pin"; don't restate it inline elsewhere.
    """
    live = (t < lengths.astype(jnp.int32))[:, None]
    return jnp.where(live, h_new, h_old), jnp.where(live, c_new, c_old)


def freeze_rows_h(t, lengths, h_new, h_old):
    """:func:`freeze_rows` for cells whose carry is ``h`` alone (GRU)."""
    live = (t < lengths.astype(jnp.int32))[:, None]
    return jnp.where(live, h_new, h_old)


def gate_stacked(params):
    """Pallas-kernel weight layout: ``[G, in, H] → ([in, G, H], [H, G, H], b)``.

    The kernels tile the hidden axis, so each tile wants the contiguous
    G-gate stack for its hidden columns (gate axis second, not first).
    Works for both cells: G=4 (:class:`LSTMParams`) and G=3
    (:class:`GRUParams`).
    """
    return (jnp.moveaxis(params.wx, 0, 1), jnp.moveaxis(params.wh, 0, 1),
            params.b)


def _pin_operands(*ops):
    """Materialize sub-fp32 matmul operands at their stated dtype.

    Inside jit, XLA fuses elementwise producers (mask·1/(1-p) scaling, fp32→
    bf16 weight/input casts) into the dot and evaluates the chain at the
    dot's higher internal precision — silently skipping the bf16 rounding
    the Pallas kernels apply when they materialize the same intermediates in
    registers.  An optimization barrier pins each operand to its rounded
    value, keeping the reference backend bit-identical to the kernels for
    bf16 activations (the int8/int4/bf16 serving precisions).  fp32 operands
    pass through untouched — rounding is unaffected, so no barrier tax.
    """
    if any(o.dtype != jnp.float32 for o in ops):
        return jax.lax.optimization_barrier(ops)
    return ops


def _gate_dot(v, w, g: int):
    """Gate ``g``'s fp32-accumulated matmul, ``[B,G,D] × [G,D,H] → [B,H]``.

    One 2-D dot per gate — the kernels' exact formulation.  A single
    batched contraction over the gate axis would be one MXU pass on TPU,
    but it accumulates in another order than the kernels' per-gate dots on
    the CPU backend (breaking bit-identity), and the CPU runtime refuses
    its bf16×bf16→fp32 form outright.
    """
    return jnp.dot(v[:, g], w[g], preferred_element_type=jnp.float32)


def lstm_step(params: LSTMParams, h: jax.Array, c: jax.Array, x: jax.Array,
              zx: jax.Array | None, zh: jax.Array | None, p: float,
              compute_dtype=None, det: jax.Array | None = None):
    """One LSTM time step with per-gate MCD masks (paper's Eq. block + DX units).

    Args:
      h, c: [B, H] carry.  x: [B, I] input at time t.
      zx: [B, 4, I] or None; zh: [B, 4, H] or None — keep-masks tied across T.
      p: dropout probability (for inverted scaling).
      det: [B] bool or None — True rows run deterministic (student fast path):
        the mask·scale is replaced by the raw view for that row only, exactly
        as the kernels do for rows carrying :data:`repro.core.mcd.STUDENT_ROW_FLAG`.
    Returns:
      (h_new, c_new), each [B, H].  c is accumulated in fp32 (the paper keeps
      c in 32-bit while everything else is 16-bit — same policy here).
    """
    cd = compute_dtype or x.dtype
    wx, wh, b = params
    # Per-gate masked views: [B, 4, I] and [B, 4, H].
    xr = jnp.broadcast_to(x[:, None, :], (x.shape[0], 4, x.shape[1])).astype(cd)
    hr = jnp.broadcast_to(h[:, None, :], (h.shape[0], 4, h.shape[1])).astype(cd)
    xg = mcd.apply_mask(xr, zx, p)
    hg = mcd.apply_mask(hr, zh, p)
    if det is not None:
        xg = jnp.where(det[:, None, None], xr, xg)
        hg = jnp.where(det[:, None, None], hr, hg)
    xg, hg, wxc, whc = _pin_operands(xg, hg, wx.astype(cd), wh.astype(cd))
    gates = [_gate_dot(xg, wxc, g) + _gate_dot(hg, whc, g)
             + b[g].astype(jnp.float32) for g in range(4)]
    i = jax.nn.sigmoid(gates[0])
    f = jax.nn.sigmoid(gates[1])
    g = jnp.tanh(gates[2])
    o = jax.nn.sigmoid(gates[3])
    c_new = f * c.astype(jnp.float32) + i * g           # fp32 cell state
    h_new = (o * jnp.tanh(c_new)).astype(h.dtype)
    return h_new, c_new.astype(c.dtype)


class GRUParams(NamedTuple):
    wx: jax.Array  # [3, in_dim, hidden]
    wh: jax.Array  # [3, hidden, hidden]
    b: jax.Array   # [3, hidden]


def init_gru(key: jax.Array, in_dim: int, hidden: int,
             dtype=jnp.float32) -> GRUParams:
    kx, kh = jax.random.split(key)
    sx = (6.0 / (in_dim + hidden)) ** 0.5
    sh = (6.0 / (2 * hidden)) ** 0.5
    return GRUParams(
        jax.random.uniform(kx, (3, in_dim, hidden), dtype, -sx, sx),
        jax.random.uniform(kh, (3, hidden, hidden), dtype, -sh, sh),
        jnp.zeros((3, hidden), dtype))


def gru_step(params: GRUParams, h: jax.Array, x: jax.Array,
             zx: jax.Array | None, zh: jax.Array | None, p: float,
             compute_dtype=None, det: jax.Array | None = None):
    """GRU step with per-gate masks (paper §III-A notes GRU drops in directly).

    Args:
      h: [B, H] carry (the GRU's entire recurrent state — no cell state).
      x: [B, I] input at time t.
      zx: [B, 3, I] or None; zh: [B, 3, H] or None — keep-masks tied across T,
        gate order (r, z, n).
      p: dropout probability (for inverted scaling).
      det: [B] bool or None — True rows run deterministic (student fast path),
        mirroring the kernels' :data:`repro.core.mcd.STUDENT_ROW_FLAG` rows.
    Returns:
      h_new [B, H].  Same dtype policy as :func:`lstm_step`: inputs and
      weights compute in ``compute_dtype`` (default: x's dtype, so bf16 in →
      bf16 matmuls) while the gate accumulations, bias adds and the convex
      ``(1-z)·n + z·h`` update run in fp32.
    """
    cd = compute_dtype or x.dtype
    wx, wh, b = params
    xr = jnp.broadcast_to(x[:, None, :], (x.shape[0], 3, x.shape[1])).astype(cd)
    hr = jnp.broadcast_to(h[:, None, :], (h.shape[0], 3, h.shape[1])).astype(cd)
    xg = mcd.apply_mask(xr, zx, p)
    hg = mcd.apply_mask(hr, zh, p)
    if det is not None:
        xg = jnp.where(det[:, None, None], xr, xg)
        hg = jnp.where(det[:, None, None], hr, hg)
    xg, hg, wxc, whc = _pin_operands(xg, hg, wx.astype(cd), wh.astype(cd))
    gx = [_gate_dot(xg, wxc, g) for g in range(3)]
    gh = [_gate_dot(hg, whc, g) for g in range(3)]
    bf = b.astype(jnp.float32)
    r = jax.nn.sigmoid(gx[0] + gh[0] + bf[0])
    zt = jax.nn.sigmoid(gx[1] + gh[1] + bf[1])
    # The candidate's bias stays outside the reset product (r gates only the
    # recurrent matmul) — the kernels replicate this placement exactly.
    n = jnp.tanh(gx[2] + r * gh[2] + bf[2])
    h_new = (1.0 - zt) * n + zt * h.astype(jnp.float32)
    return h_new.astype(h.dtype)
