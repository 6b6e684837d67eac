"""Pallas TPU kernel: sequence-fused Bayesian LSTM layer — the paper's Fig. 5.

:mod:`repro.kernels.mcd_lstm` fuses one *timestep* of the Bayesian LSTM
datapath; scanning it over T re-enters the kernel per step and re-fetches the
gate weights every iteration — exactly the weight-traffic the paper's FPGA
avoids by keeping the datapath resident while the sequence streams through
(wave pipelining).  This kernel is the TPU analogue of that residency:

* The sequence runs **time-major** (``[T, B, I]``) over the grid
  ``(B/bb, T/tb)``, time an ``"arbitrary"`` (sequential) dimension.  Each
  program takes a ``[tb, bb, I]`` block of ``tb`` timesteps and loops over
  them in-kernel, writing a ``[tb, bb, H]`` output block.  The minor two
  block dims are ``(bb, I)`` / ``(bb, H)`` — batch rows on sublanes, features
  on lanes — which the TPU's (8, 128) block rule accepts; the time axis is
  a leading dim with no tiling constraint, so ``tb`` is sized from a VMEM
  budget (:func:`time_block`) rather than from alignment.
* The weight BlockSpecs map every grid step to the same block, so Pallas's
  revisiting semantics fetch ``wx [I,4,H]`` / ``wh [H,4,H]`` into VMEM
  **once**; only the input blocks stream.
* ``(h, c)`` live in VMEM scratch across time blocks (seeded at the first
  block), with ``c`` in fp32 — the paper's 32-bit cell-state policy.
* The per-gate Bernoulli keep-masks are recomputed in-register each step from
  the counter PRNG.  Masks are tied across T (paper §II-B), so the 8 stream
  keys from :func:`repro.kernels.mcd_lstm.gate_keys` never change and every
  step reproduces bit-identical masks — same draws as the per-step kernel and
  the jnp reference.

Unlike the step kernel there is no hidden-tile grid axis: timestep t needs
*all* H columns of ``h_{t-1}`` for the recurrent matmul, so tiling H across
sequentially-revisited grid programs would either break the dependency
(time-innermost order) or re-fetch weights per step (tile-innermost order).
One program therefore owns the full hidden width of its batch tile — fine for
the paper's RNN regime (H up to a few hundred; weights ≈ 8·H·(I+H) bytes of
VMEM in bf16).

Streaming extensions (continuous-monitoring serving):

* ``h0`` / ``c0`` seed the scratch at the first time block instead of zeros,
  so a session resumes mid-sequence exactly where a previous chunk left off.
  ``c0`` is consumed in fp32 — the fp32 cell state round-trips losslessly
  across chunk boundaries, keeping chunked == unchunked bit-identical.
* ``lengths`` freezes a row's ``(h, c)`` once ``t >= lengths[row]``: ragged
  chunks from concurrent sessions pad to a common T and still come back with
  each row's state at *its own* last real step, in one launch.  A T that is
  not a multiple of ``tb`` pads up to one and freezes every row at T the
  same way.
* A ``block_b`` that does not divide B pads the batch up to the next block
  multiple (outputs sliced back) instead of degrading to ``bb = 1`` for prime
  batch sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quantize, resolve_interpret
from repro.kernels.mcd_lstm import _gate_mask

#: VMEM bytes the streamed time blocks (input + output, double-buffered) may
#: take.  With the resident weights beside them this keeps every launch of
#: the scheduler's ladder (T up to 512, H up to a few hundred) under the
#: compiler's default scoped-VMEM limit (16 MiB on v5e).
STREAM_VMEM_BYTES = 4 << 20


def _padded(n: int, m: int) -> int:
    return -(-n // m) * m


def time_block(T: int, bb: int, in_dim: int, hidden: int,
               itemsize: int) -> int:
    """Timesteps per grid program for a ``[T, bb, *]`` time-major launch.

    A VMEM tile pads its minor dim to 128 lanes and its second-minor to 8
    sublanes, so one timestep of the ``[bb, I]`` input and ``[bb, H]``
    output blocks costs ``bb·(⌈I⌉₁₂₈ + ⌈H⌉₁₂₈)·itemsize`` bytes, twice for
    double buffering — at the paper's I=1, H=8 that is 256 lanes for 9
    useful ones.  ``tb`` is the largest step count within
    :data:`STREAM_VMEM_BYTES`, evened out over the blocks T needs so that
    the padded tail is as short as possible (T=140 at bb=128 fp32: 9
    blocks of 16, 4 padded steps).
    """
    per_step = (2 * _padded(bb, 8) * (_padded(in_dim, 128)
                                       + _padded(hidden, 128)) * itemsize)
    cap = max(1, STREAM_VMEM_BYTES // per_step)
    n_blocks = -(-T // cap)
    return -(-T // n_blocks)


def _kernel(*refs,
            p_drop: float, in_dim: int, hidden: int, t_block: int,
            weight_bits: int | None):
    # Quantized runs insert two [4, H] fp32 scale operands after the weights;
    # everything else (ref order, outputs, scratch) is unchanged.
    if weight_bits is None:
        (rows_ref, keys_ref, lens_ref, x_ref, h0_ref, c0_ref,
         wx_ref, wh_ref, b_ref,
         ys_ref, ht_ref, ct_ref, h_scr, c_scr) = refs
    else:
        (rows_ref, keys_ref, lens_ref, x_ref, h0_ref, c0_ref,
         wx_ref, wh_ref, sx_ref, sh_ref, b_ref,
         ys_ref, ht_ref, ct_ref, h_scr, c_scr) = refs
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _reset():
        # Carried-state entry point: a fresh sequence passes zeros here; a
        # resumed session passes the previous chunk's (h_T, c_T).
        h_scr[...] = h0_ref[...]
        c_scr[...] = c0_ref[...]

    rows = rows_ref[...][:, 0]
    act = x_ref.dtype
    if weight_bits is None:
        wxv, whv = wx_ref[...], wh_ref[...]
    else:
        # In-register dequant of the int-resident weights: the canonical
        # q·scale expression (repro.kernels.quantize), cast to the activation
        # dtype — exactly the values fake_quant hands the other backends.
        wxv = quantize.kernel_weight(wx_ref[...], sx_ref[...], weight_bits,
                                     hidden=hidden, act_dtype=act)
        whv = quantize.kernel_weight(wh_ref[...], sh_ref[...], weight_bits,
                                     hidden=hidden, act_dtype=act)
    # int32 rows: a negative id carries mcd.STUDENT_ROW_FLAG — that row runs
    # deterministic (dropout off), co-batched with the Bayesian rows.
    det = (rows < 0)[:, None]
    scale = jnp.asarray(1.0 / (1.0 - p_drop), act) if p_drop > 0 else None
    t0 = blk * t_block

    def step(s, carry):
        h, c = carry                # [bb, H] act dtype / fp32
        x = x_ref[s]                # [bb, I] — this step's input slice
        gates = []
        for g in range(4):
            xg, hg = x, h
            if p_drop > 0.0:
                # Same (key, row, col) → bit mapping as the step kernel; keys
                # are t-independent so recomputing here *is* tying across
                # time.
                kx = keys_ref[0, g]
                kh = keys_ref[0, 4 + g]
                mx = _gate_mask(kx, rows, 0, x.shape, in_dim, p_drop)
                mh = _gate_mask(kh, rows, 0, h.shape, hidden, p_drop)
                xg = jnp.where(mx, x * scale, jnp.zeros_like(x))
                hg = jnp.where(mh, h * scale, jnp.zeros_like(h))
                xg = jnp.where(det, x, xg)
                hg = jnp.where(det, h, hg)
            acc = jnp.dot(xg, wxv[:, g, :], preferred_element_type=jnp.float32)
            acc += jnp.dot(hg, whv[:, g, :],
                           preferred_element_type=jnp.float32)
            gates.append(acc + b_ref[g, :].astype(jnp.float32))
        i = jax.nn.sigmoid(gates[0])
        f = jax.nn.sigmoid(gates[1])
        g_ = jnp.tanh(gates[2])
        o = jax.nn.sigmoid(gates[3])
        c_new = f * c + i * g_
        h_new = (o * jnp.tanh(c_new)).astype(h.dtype)
        # Rows whose chunk ended before this step keep their carried state
        # — the final (h_T, c_T) are each row's state at its own length.
        # Unconditional, so a launch with and without ``lengths`` runs the
        # same ops and rounds the same way.
        live = t0 + s < lens_ref[...]              # [bb, 1]
        c_new = jnp.where(live, c_new, c)
        h_new = jnp.where(live, h_new, h)
        ys_ref[s] = h_new.astype(ys_ref.dtype)
        return h_new, c_new

    h, c = jax.lax.fori_loop(0, t_block, step, (h_scr[...], c_scr[...]))
    h_scr[...] = h
    c_scr[...] = c
    ht_ref[...] = h.astype(ht_ref.dtype)
    ct_ref[...] = c.astype(ct_ref.dtype)


@functools.partial(jax.jit, static_argnames=("p_drop", "block_b", "interpret",
                                             "weight_bits"))
def mcd_lstm_seq(x_seq: jax.Array, wx: jax.Array, wh: jax.Array, b: jax.Array,
                 rows: jax.Array, keys: jax.Array, p_drop: float, *,
                 h0: jax.Array | None = None, c0: jax.Array | None = None,
                 lengths: jax.Array | None = None,
                 weight_bits: int | None = None,
                 wx_scale: jax.Array | None = None,
                 wh_scale: jax.Array | None = None,
                 block_b: int = 128, interpret: bool | None = None):
    """Sequence-fused Bayesian LSTM layer, optionally resuming carried state.

    x_seq: [B, T, I]; wx: [I, 4, H]; wh: [H, 4, H]; b: [4, H];
    rows: [B] mask row ids; keys: [1, 8] from
    :func:`repro.kernels.mcd_lstm.gate_keys`.
    h0 [B, H] / c0 [B, H] seed the carried state (zeros when omitted — a
    fresh sequence); c0 is accumulated in fp32 regardless of input dtype.
    lengths [B] (int) freezes a row's state at its own chunk length so ragged
    chunks can pad to a common T in one launch.
    weight_bits 8/4 switches to quantized weights: ``wx``/``wh`` carry int8
    codes (int4: nibble-packed uint8, last axis ``ceil(H/2)``) and
    ``wx_scale``/``wh_scale`` the [4, H] fp32 per-output-channel scales; the
    kernel dequantizes in-register, so the VMEM-resident weight bytes drop
    ~2×/4× vs bf16 while the gate math stays fp32-accumulated.
    ``interpret`` None runs natively on a TPU backend and in the Pallas
    interpreter elsewhere (:func:`repro.kernels.resolve_interpret`).
    Returns (ys [B, T, H], h_T [B, H], c_T [B, H] fp32); with ``lengths``,
    (h_T, c_T) is each row's state at ``t = lengths[row]`` and
    ``ys[:, t >= lengths[row]]`` repeats the frozen h.
    """
    B, T, I = x_seq.shape
    H = wh.shape[0]
    if weight_bits is not None and (wx_scale is None or wh_scale is None):
        raise ValueError("weight_bits set but wx_scale/wh_scale missing")
    bb = min(block_b, B)
    tb = time_block(T, bb, I, H, x_seq.dtype.itemsize)
    Tp = _padded(T, tb)
    h0 = jnp.zeros((B, H), x_seq.dtype) if h0 is None else h0.astype(x_seq.dtype)
    c0 = (jnp.zeros((B, H), jnp.float32) if c0 is None
          else c0.astype(jnp.float32))
    lens = (jnp.full((B,), T, jnp.int32) if lengths is None
            else lengths.astype(jnp.int32))
    rows2 = rows.astype(jnp.int32).reshape(B, 1)
    xt = jnp.swapaxes(x_seq, 0, 1)       # [T, B, I] time-major
    pad = -B % bb        # pad to the block multiple (prime/odd batch sizes)
    if pad:
        zb = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        rows2, h0, c0, lens = map(zb, (rows2, h0, c0, lens))
    if pad or Tp != T:
        xt = jnp.pad(xt, ((0, Tp - T), (0, pad), (0, 0)))
    Bp = B + pad
    lens2 = lens.reshape(Bp, 1)
    grid = (Bp // bb, Tp // tb)
    Wl = wx.shape[-1]    # H, or ceil(H/2) when int4 nibble-packed
    w_specs = [
        pl.BlockSpec((I, 4, Wl), lambda i, t: (0, 0, 0)),      # wx — resident
        pl.BlockSpec((H, 4, Wl), lambda i, t: (0, 0, 0)),      # wh — resident
    ]
    w_ops = (wx, wh)
    if weight_bits is not None:
        w_specs += [pl.BlockSpec((4, H), lambda i, t: (0, 0)),  # wx scales
                    pl.BlockSpec((4, H), lambda i, t: (0, 0))]  # wh scales
        w_ops += (wx_scale, wh_scale)
    ys, hT, cT = pl.pallas_call(
        functools.partial(_kernel, p_drop=p_drop, in_dim=I, hidden=H,
                          t_block=tb, weight_bits=weight_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, 1), lambda i, t: (i, 0)),         # rows
            pl.BlockSpec((1, 8), lambda i, t: (0, 0)),          # keys
            pl.BlockSpec((bb, 1), lambda i, t: (i, 0)),         # lengths
            pl.BlockSpec((tb, bb, I), lambda i, t: (t, i, 0)),  # x time block
            pl.BlockSpec((bb, H), lambda i, t: (i, 0)),         # h0
            pl.BlockSpec((bb, H), lambda i, t: (i, 0)),         # c0 (fp32)
            *w_specs,
            pl.BlockSpec((4, H), lambda i, t: (0, 0)),          # bias
        ],
        out_specs=[
            pl.BlockSpec((tb, bb, H), lambda i, t: (t, i, 0)),
            pl.BlockSpec((bb, H), lambda i, t: (i, 0)),
            pl.BlockSpec((bb, H), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, Bp, H), x_seq.dtype),
            jax.ShapeDtypeStruct((Bp, H), x_seq.dtype),
            jax.ShapeDtypeStruct((Bp, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, H), x_seq.dtype),    # h carry
            pltpu.VMEM((bb, H), jnp.float32),    # c carry (32-bit policy)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(rows2, keys, lens2, xt, h0, c0, *w_ops, b)
    ys = jnp.swapaxes(ys[:T, :B], 0, 1)
    return ys, hT[:B], cT[:B]
