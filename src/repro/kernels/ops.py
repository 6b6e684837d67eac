"""jit'd high-level wrappers around the Pallas kernels.

``interpret`` passes through to the kernels, where None resolves to native
lowering on a TPU backend and the interpreter elsewhere (CPU validation
path; :func:`repro.kernels.resolve_interpret`).  The framework's model
code uses the pure-jnp mirrors by default (sharding-friendly under GSPMD);
these wrappers are the TPU hot-path entry points and the unit under test in
``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import cells, mcd
from repro.core.rnn import CELLS  # noqa: F401 — single-source cell registry
from repro.kernels import (bernoulli_mask, mcd_gru, mcd_gru_seq, mcd_lstm,
                           mcd_lstm_seq, mcd_matmul, quantize)

#: Stack-layer execution paths (see ``repro.core.rnn.run_stack``):
#: "reference"    pure-jnp cells (sharding-friendly, the numerical oracle)
#: "pallas_step"  fused cell kernel re-entered per timestep via lax.scan
#: "pallas_seq"   sequence-fused kernel — weights resident across all T
LSTM_BACKENDS = ("reference", "pallas_step", "pallas_seq")


def flash_decode_attention(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, pos, **kw) -> jax.Array:
    """Fused decode attention (EXPERIMENTS.md §Perf Cell C hot path)."""
    from repro.kernels import decode_attn
    return decode_attn.decode_attention(q, k_cache, v_cache, pos, **kw)


def mcd_dense(x: jax.Array, w: jax.Array, rows: jax.Array, seed, layer: int,
              site: int, p_drop: float, **kw) -> jax.Array:
    """Fused masked dense: y = (x ⊙ z/(1-p)) @ W with the site-keyed stream."""
    key = mcd.mask_key(seed, layer, mcd.KIND_FEAT, site)
    return mcd_matmul.mcd_matmul(x, w, rows, key, p_drop, **kw)


def mcd_mask_apply(x: jax.Array, rows: jax.Array, seed, layer: int, site: int,
                   p_drop: float, **kw) -> jax.Array:
    key = mcd.mask_key(seed, layer, mcd.KIND_FEAT, site)
    return bernoulli_mask.masked_activation(x, rows, key, p_drop, **kw)


@functools.partial(jax.jit, static_argnames=("p_drop", "interpret"))
def fused_lstm_layer(wx4: jax.Array, wh4: jax.Array, b: jax.Array,
                     x_seq: jax.Array, rows: jax.Array, seed, layer: int,
                     p_drop: float, h0: jax.Array | None = None,
                     c0: jax.Array | None = None,
                     lengths: jax.Array | None = None,
                     interpret: bool | None = None):
    """Scan the fused cell kernel over time (paper Fig. 5 TS pipelining).

    wx4: [I, 4, H]; wh4: [H, 4, H]; b: [4, H]; x_seq: [B, T, I].
    ``h0``/``c0`` resume carried state (zeros when omitted); ``lengths``
    freezes each row's state at its own chunk length (ragged batching).
    Returns (outputs [B, T, H], (h_T, c_T fp32)).
    """
    B, T, _ = x_seq.shape
    H = wh4.shape[0]
    keys = mcd_lstm.gate_keys(seed, layer)
    h0 = jnp.zeros((B, H), x_seq.dtype) if h0 is None else h0.astype(x_seq.dtype)
    c0 = (jnp.zeros((B, H), jnp.float32) if c0 is None
          else c0.astype(jnp.float32))

    def step(carry, xt):
        h, c = carry
        x_t, t = xt
        h_new, c_new = mcd_lstm.mcd_lstm_step(x_t, h, c, wx4, wh4, b, rows,
                                              keys, p_drop,
                                              interpret=interpret)
        if lengths is not None:
            h_new, c_new = cells.freeze_rows(t, lengths, h_new, c_new, h, c)
        return (h_new, c_new), h_new

    ts = jnp.arange(T, dtype=jnp.int32)
    (hT, cT), ys = jax.lax.scan(step, (h0, c0),
                                (jnp.swapaxes(x_seq, 0, 1), ts))
    return jnp.swapaxes(ys, 0, 1), (hT, cT)


@functools.partial(jax.jit, static_argnames=("p_drop", "interpret",
                                             "weight_bits"))
def fused_lstm_seq(wx4: jax.Array, wh4: jax.Array, b: jax.Array,
                   x_seq: jax.Array, rows: jax.Array, seed, layer: int,
                   p_drop: float, h0: jax.Array | None = None,
                   c0: jax.Array | None = None,
                   lengths: jax.Array | None = None,
                   weight_bits: int | None = None,
                   wx_scale: jax.Array | None = None,
                   wh_scale: jax.Array | None = None,
                   interpret: bool | None = None):
    """One kernel launch for the whole sequence (paper Fig. 5 wave pipelining).

    Same contract as :func:`fused_lstm_layer` — wx4: [I, 4, H]; wh4: [H, 4, H];
    b: [4, H]; x_seq: [B, T, I]; returns (outputs [B, T, H], (h_T, c_T)) —
    but the weights stay VMEM-resident across all T timesteps instead of being
    re-fetched per scan iteration.  ``h0``/``c0``/``lengths`` carry streaming
    session state into and out of the launch (see ``mcd_lstm_seq``).
    With ``weight_bits`` 8/4, ``wx4``/``wh4`` carry quantized codes and
    ``wx_scale``/``wh_scale`` the [4, H] fp32 scales (dequant in-register).
    """
    keys = mcd_lstm.gate_keys(seed, layer)
    ys, hT, cT = mcd_lstm_seq.mcd_lstm_seq(x_seq, wx4, wh4, b, rows, keys,
                                           p_drop, h0=h0, c0=c0,
                                           lengths=lengths,
                                           weight_bits=weight_bits,
                                           wx_scale=wx_scale,
                                           wh_scale=wh_scale,
                                           interpret=interpret)
    return ys, (hT, cT)


def _precision_weights(wx, wh, x_seq, precision, *, seq: bool):
    """Apply a serving precision to gate-stacked weights + the input.

    Returns ``(wx, wh, x_seq, qkw)`` where ``qkw`` holds the extra kwargs the
    sequence-kernel wrappers take when the weights are quantized codes.  The
    step path gets the *dequantized* weights instead (same canonical q·scale
    values, applied outside the kernel), so every backend sees identical
    weight values at identical dtypes — the bit-identity contract.
    """
    if precision is None:
        return wx, wh, x_seq, {}
    act = quantize.activation_dtype(precision, x_seq.dtype)
    x_seq = x_seq.astype(act)
    if precision not in quantize.QUANTIZED:
        return wx.astype(act), wh.astype(act), x_seq, {}
    bits = quantize.WEIGHT_BITS[precision]
    qx, sx = quantize.quantize(wx, bits, axis=0)
    qh, sh = quantize.quantize(wh, bits, axis=0)
    if seq:
        return (quantize.packed_weight(qx, bits),
                quantize.packed_weight(qh, bits), x_seq,
                dict(weight_bits=bits, wx_scale=sx, wh_scale=sh))
    return (quantize.dequantize(qx, sx, axis=0).astype(act),
            quantize.dequantize(qh, sh, axis=0).astype(act), x_seq, {})


@functools.partial(jax.jit, static_argnames=("p_drop", "seq", "interpret",
                                             "precision"))
def lstm_stack_layer(wx: jax.Array, wh: jax.Array, b: jax.Array,
                     x_seq: jax.Array, rows: jax.Array, seed, layer,
                     p_drop: float, *, seq: bool,
                     initial_state=None, lengths: jax.Array | None = None,
                     precision: str | None = None,
                     interpret: bool | None = None):
    """Core-layout entry for ``run_stack``'s Pallas backends.

    Takes ``repro.core.cells.LSTMParams`` layout (wx: [4, I, H]; wh:
    [4, H, H]) and transposes to the kernels' gate-stacked layout *inside*
    jit, so repeated calls (the S MC-sample loop) don't pay an eager
    per-call transpose.  ``layer`` is traced (it only feeds the counter-PRNG
    key fold), so same-shaped layers share one compile.  ``seq`` picks
    sequence- vs step-fusion.  ``initial_state`` is an optional ``(h0, c0)``
    pair resuming a streaming session's carried state.  ``precision``
    (fp32/bf16/int8/int4) quantizes or casts the fp32 master weights
    in-graph — int8/int4 run the seq kernel with int-resident weights and
    in-register dequant, the step kernel with the same dequantized values.
    """
    wx4, wh4, b = cells.gate_stacked(cells.LSTMParams(wx, wh, b))
    wx4, wh4, x_seq, qkw = _precision_weights(wx4, wh4, x_seq, precision,
                                              seq=seq)
    h0, c0 = initial_state if initial_state is not None else (None, None)
    fn = fused_lstm_seq if seq else fused_lstm_layer
    return fn(wx4, wh4, b, x_seq, rows, seed, layer, p_drop, h0=h0, c0=c0,
              lengths=lengths, interpret=interpret, **qkw)


@functools.partial(jax.jit, static_argnames=("p_drop", "interpret"))
def fused_gru_layer(wx3: jax.Array, wh3: jax.Array, b: jax.Array,
                    x_seq: jax.Array, rows: jax.Array, seed, layer: int,
                    p_drop: float, h0: jax.Array | None = None,
                    lengths: jax.Array | None = None,
                    interpret: bool | None = None):
    """Scan the fused GRU cell kernel over time (per-step fusion baseline).

    wx3: [I, 3, H]; wh3: [H, 3, H]; b: [3, H]; x_seq: [B, T, I].
    ``h0`` resumes carried state (zeros when omitted); ``lengths`` freezes
    each row's state at its own chunk length (ragged batching).
    Returns (outputs [B, T, H], (h_T,)) — the carry is a 1-tuple because the
    GRU's entire recurrent state is ``h``.
    """
    B, T, _ = x_seq.shape
    H = wh3.shape[0]
    keys = mcd_gru.gate_keys(seed, layer)
    h0 = jnp.zeros((B, H), x_seq.dtype) if h0 is None else h0.astype(x_seq.dtype)

    def step(h, xt):
        x_t, t = xt
        h_new = mcd_gru.mcd_gru_step(x_t, h, wx3, wh3, b, rows, keys, p_drop,
                                     interpret=interpret)
        if lengths is not None:
            h_new = cells.freeze_rows_h(t, lengths, h_new, h)
        return h_new, h_new

    ts = jnp.arange(T, dtype=jnp.int32)
    hT, ys = jax.lax.scan(step, h0, (jnp.swapaxes(x_seq, 0, 1), ts))
    return jnp.swapaxes(ys, 0, 1), (hT,)


@functools.partial(jax.jit, static_argnames=("p_drop", "interpret",
                                             "weight_bits"))
def fused_gru_seq(wx3: jax.Array, wh3: jax.Array, b: jax.Array,
                  x_seq: jax.Array, rows: jax.Array, seed, layer: int,
                  p_drop: float, h0: jax.Array | None = None,
                  lengths: jax.Array | None = None,
                  weight_bits: int | None = None,
                  wx_scale: jax.Array | None = None,
                  wh_scale: jax.Array | None = None,
                  interpret: bool | None = None):
    """One kernel launch for the whole GRU sequence (weights VMEM-resident).

    Same contract as :func:`fused_gru_layer`, but the 3-gate weights stay
    resident across all T timesteps instead of being re-fetched per scan
    iteration (the ``mcd_gru_seq`` kernel).  With ``weight_bits`` 8/4,
    ``wx3``/``wh3`` carry quantized codes and ``wx_scale``/``wh_scale`` the
    [3, H] fp32 scales (dequant in-register).
    """
    keys = mcd_gru.gate_keys(seed, layer)
    ys, hT = mcd_gru_seq.mcd_gru_seq(x_seq, wx3, wh3, b, rows, keys, p_drop,
                                     h0=h0, lengths=lengths,
                                     weight_bits=weight_bits,
                                     wx_scale=wx_scale, wh_scale=wh_scale,
                                     interpret=interpret)
    return ys, (hT,)


@functools.partial(jax.jit, static_argnames=("p_drop", "seq", "interpret",
                                             "precision"))
def gru_stack_layer(wx: jax.Array, wh: jax.Array, b: jax.Array,
                    x_seq: jax.Array, rows: jax.Array, seed, layer,
                    p_drop: float, *, seq: bool,
                    initial_state=None, lengths: jax.Array | None = None,
                    precision: str | None = None,
                    interpret: bool | None = None):
    """Core-layout GRU entry for ``run_stack``'s Pallas backends.

    Mirrors :func:`lstm_stack_layer`: takes ``repro.core.cells.GRUParams``
    layout (wx: [3, I, H]; wh: [3, H, H]), transposes to the gate-stacked
    kernel layout inside jit, traces ``layer`` (shared compiles across
    same-shaped layers).  ``initial_state`` is the 1-tuple ``(h0,)`` carry
    a streaming session stores for a GRU layer.  ``precision`` quantizes or
    casts the fp32 master weights in-graph, as in the LSTM entry.
    """
    wx3, wh3, b = cells.gate_stacked(cells.GRUParams(wx, wh, b))
    wx3, wh3, x_seq, qkw = _precision_weights(wx3, wh3, x_seq, precision,
                                              seq=seq)
    (h0,) = initial_state if initial_state is not None else (None,)
    fn = fused_gru_seq if seq else fused_gru_layer
    return fn(wx3, wh3, b, x_seq, rows, seed, layer, p_drop, h0=h0,
              lengths=lengths, interpret=interpret, **qkw)
