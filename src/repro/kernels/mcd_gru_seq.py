"""Pallas TPU kernel: sequence-fused Bayesian GRU layer.

The GRU counterpart of :mod:`repro.kernels.mcd_lstm_seq` — same residency
story (weights fetched into VMEM once, the sequence streams through the
resident datapath), same time-major layout and time blocking, same
streaming contract, one structural difference: the GRU's entire recurrent
state is ``h``, so there is a single VMEM scratch carry and a single
carried-state operand.

* Time-major grid ``(B/bb, T/tb)`` with time as an ``"arbitrary"``
  (sequential) dimension; each program loops over the ``tb`` steps of its
  ``[tb, bb, I]`` input block in-kernel (``tb`` from
  :func:`repro.kernels.mcd_lstm_seq.time_block`).  The weight BlockSpecs map
  every grid step to the same block so ``wx [I,3,H]`` / ``wh [H,3,H]`` are
  fetched once.
* ``h`` lives in VMEM scratch across time blocks (seeded from ``h0`` at the
  first block), stored in the activation dtype each step — exactly the
  per-step rounding of :func:`repro.core.cells.gru_step`, which is what
  makes a chunk boundary (bf16 ``h`` out, bf16 ``h`` back in) lossless and
  chunked == unchunked bit-identical.  The gate math runs in fp32.
* The 3-gate Bernoulli keep-masks (r, z, n) are recomputed in-register each
  step from the 6 ``gate_keys`` streams; keys carry no time coordinate, so
  recomputation is the paper's tied-across-T semantics.
* ``lengths`` freezes a row's ``h`` once ``t >= lengths[row]`` (ragged
  chunks pad to a common T, each row comes back at its own last real step;
  a T padded up to the time block freezes at T the same way);
  ``block_b`` pads a non-dividing batch up to the block multiple.

No hidden-tile grid axis, for the same dependency reason as the LSTM
sequence kernel (docs/kernels.md): step t needs all H columns of
``h_{t-1}`` — and for the GRU twice over, since ``h`` feeds both the
recurrent matmuls and the ``z·h`` convex update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quantize, resolve_interpret
from repro.kernels.mcd_gru import _gru_update
from repro.kernels.mcd_lstm_seq import _padded, time_block


def _kernel(*refs, p_drop: float, in_dim: int, hidden: int, t_block: int,
            weight_bits: int | None):
    # Quantized runs insert two [3, H] fp32 scale operands after the weights;
    # everything else (ref order, outputs, scratch) is unchanged.
    if weight_bits is None:
        (rows_ref, keys_ref, lens_ref, x_ref, h0_ref, wx_ref, wh_ref,
         b_ref, ys_ref, ht_ref, h_scr) = refs
    else:
        (rows_ref, keys_ref, lens_ref, x_ref, h0_ref, wx_ref, wh_ref,
         sx_ref, sh_ref, b_ref, ys_ref, ht_ref, h_scr) = refs
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _reset():
        # Carried-state entry point: a fresh sequence passes zeros here; a
        # resumed session passes the previous chunk's h_T.
        h_scr[...] = h0_ref[...]

    rows = rows_ref[...][:, 0]
    if weight_bits is None:
        wxv, whv = wx_ref[...], wh_ref[...]
    else:
        # In-register dequant of the int-resident weights: the canonical
        # q·scale expression (repro.kernels.quantize), cast to the activation
        # dtype — exactly the values fake_quant hands the other backends.
        wxv = quantize.kernel_weight(wx_ref[...], sx_ref[...], weight_bits,
                                     hidden=hidden, act_dtype=x_ref.dtype)
        whv = quantize.kernel_weight(wh_ref[...], sh_ref[...], weight_bits,
                                     hidden=hidden, act_dtype=x_ref.dtype)
    t0 = blk * t_block

    def step(s, h):
        x = x_ref[s]                # [bb, I] — this step's input slice
        # Gate body shared with the step kernel; the keys are t-independent
        # so recomputing the masks here every step *is* tying them across
        # time.
        h_new = _gru_update(x, h, h, rows, keys_ref, wxv, whv, b_ref,
                            p_drop=p_drop, in_dim=in_dim,
                            hidden=hidden).astype(h.dtype)
        # Rows whose chunk ended before this step keep their carried state
        # — the final h_T output is each row's state at its own length.
        # Unconditional, so a launch with and without ``lengths`` runs the
        # same ops and rounds the same way.
        live = t0 + s < lens_ref[...]              # [bb, 1]
        h_new = jnp.where(live, h_new, h)
        ys_ref[s] = h_new.astype(ys_ref.dtype)
        return h_new

    h = jax.lax.fori_loop(0, t_block, step, h_scr[...])
    h_scr[...] = h
    ht_ref[...] = h.astype(ht_ref.dtype)


@functools.partial(jax.jit, static_argnames=("p_drop", "block_b", "interpret",
                                             "weight_bits"))
def mcd_gru_seq(x_seq: jax.Array, wx: jax.Array, wh: jax.Array, b: jax.Array,
                rows: jax.Array, keys: jax.Array, p_drop: float, *,
                h0: jax.Array | None = None,
                lengths: jax.Array | None = None,
                weight_bits: int | None = None,
                wx_scale: jax.Array | None = None,
                wh_scale: jax.Array | None = None,
                block_b: int = 128, interpret: bool | None = None):
    """Sequence-fused Bayesian GRU layer, optionally resuming carried state.

    x_seq: [B, T, I]; wx: [I, 3, H]; wh: [H, 3, H]; b: [3, H];
    rows: [B] mask row ids; keys: [1, 6] from
    :func:`repro.kernels.mcd_gru.gate_keys`.
    h0 [B, H] seeds the carried state (zeros when omitted — a fresh
    sequence); it round-trips in the activation dtype, the GRU's only carry.
    lengths [B] (int) freezes a row's state at its own chunk length so ragged
    chunks can pad to a common T in one launch.
    weight_bits 8/4 switches to quantized weights: ``wx``/``wh`` carry int8
    codes (int4: nibble-packed uint8, last axis ``ceil(H/2)``) and
    ``wx_scale``/``wh_scale`` the [3, H] fp32 per-output-channel scales; the
    kernel dequantizes in-register, so the VMEM-resident weight bytes drop
    ~2×/4× vs bf16 while the gate math stays fp32-accumulated.
    ``interpret`` None runs natively on a TPU backend and in the Pallas
    interpreter elsewhere (:func:`repro.kernels.resolve_interpret`).
    Returns (ys [B, T, H], h_T [B, H]); with ``lengths``, h_T is each row's
    state at ``t = lengths[row]`` and ``ys[:, t >= lengths[row]]`` repeats
    the frozen h.
    """
    B, T, I = x_seq.shape
    H = wh.shape[0]
    if weight_bits is not None and (wx_scale is None or wh_scale is None):
        raise ValueError("weight_bits set but wx_scale/wh_scale missing")
    bb = min(block_b, B)
    tb = time_block(T, bb, I, H, x_seq.dtype.itemsize)
    Tp = _padded(T, tb)
    h0 = jnp.zeros((B, H), x_seq.dtype) if h0 is None else h0.astype(x_seq.dtype)
    lens = (jnp.full((B,), T, jnp.int32) if lengths is None
            else lengths.astype(jnp.int32))
    rows2 = rows.astype(jnp.int32).reshape(B, 1)
    xt = jnp.swapaxes(x_seq, 0, 1)       # [T, B, I] time-major
    pad = -B % bb        # pad to the block multiple (prime/odd batch sizes)
    if pad:
        zb = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        rows2, h0, lens = map(zb, (rows2, h0, lens))
    if pad or Tp != T:
        xt = jnp.pad(xt, ((0, Tp - T), (0, pad), (0, 0)))
    Bp = B + pad
    lens2 = lens.reshape(Bp, 1)
    grid = (Bp // bb, Tp // tb)
    Wl = wx.shape[-1]    # H, or ceil(H/2) when int4 nibble-packed
    w_specs = [
        pl.BlockSpec((I, 3, Wl), lambda i, t: (0, 0, 0)),      # wx — resident
        pl.BlockSpec((H, 3, Wl), lambda i, t: (0, 0, 0)),      # wh — resident
    ]
    w_ops = (wx, wh)
    if weight_bits is not None:
        w_specs += [pl.BlockSpec((3, H), lambda i, t: (0, 0)),  # wx scales
                    pl.BlockSpec((3, H), lambda i, t: (0, 0))]  # wh scales
        w_ops += (wx_scale, wh_scale)
    ys, hT = pl.pallas_call(
        functools.partial(_kernel, p_drop=p_drop, in_dim=I, hidden=H,
                          t_block=tb, weight_bits=weight_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1), lambda i, t: (i, 0)),         # rows
            pl.BlockSpec((1, 6), lambda i, t: (0, 0)),          # keys
            pl.BlockSpec((bb, 1), lambda i, t: (i, 0)),         # lengths
            pl.BlockSpec((tb, bb, I), lambda i, t: (t, i, 0)),  # x time block
            pl.BlockSpec((bb, H), lambda i, t: (i, 0)),         # h0
            *w_specs,
            pl.BlockSpec((3, H), lambda i, t: (0, 0)),          # bias
        ],
        out_specs=[
            pl.BlockSpec((tb, bb, H), lambda i, t: (t, i, 0)),
            pl.BlockSpec((bb, H), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, Bp, H), x_seq.dtype),
            jax.ShapeDtypeStruct((Bp, H), x_seq.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, H), x_seq.dtype),    # h carry — the whole state
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(rows2, keys, lens2, xt, h0, *w_ops, b)
    ys = jnp.swapaxes(ys[:T, :B], 0, 1)
    return ys, hT[:B]
