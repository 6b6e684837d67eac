"""Per-channel symmetric weight quantization for the serving kernels.

The paper's co-design treats bit-width as a first-class axis: the FPGA
design runs 16-bit fixed point and the DSE trades precision against DSPs
and accuracy (§IV, Tables I/II).  The TPU serving analogue is *weight*
quantization in the sequence-fused kernels: the VMEM-resident byte budget
(docs/kernels.md — weights ≈ 2·G·H·(I+H) bytes in bf16) is what bounds
the on-chip hidden width, so int8 halves and packed int4 quarters the
residency footprint while activations stay bf16 and accumulation fp32.

Scheme (one definition, shared by every backend — bit-identity depends on
it):

* **Symmetric, per-output-channel scales.**  For a gate-stacked weight
  ``w[..., G, H]`` each output channel ``(g, h)`` gets
  ``scale[g, h] = max_i |w[i, g, h]| / qmax`` with ``qmax = 2^(bits-1)-1``
  (127 for int8, 7 for int4); ``q = clip(round(w / scale), ±qmax)``.
  ``round`` is round-half-to-even and the reduction axis is always the
  *contraction* dim, so quantizing in kernel layout ``[I, G, H]`` (axis 0)
  or core layout ``[G, I, H]`` (axis 1) yields bit-identical ``(q, scale)``
  — max/divide/round are elementwise or exact reductions over the same
  element sets.
* **Canonical dequant** ``w_deq = (q.astype(f32) * scale).astype(act)``.
  The sequence kernels apply it in-register to their VMEM-resident int
  operands; the step-kernel wrapper and the jnp reference apply the same
  jnp expression outside — identical values, so the three backends stay
  bit-identical per precision.
* **int4 packs two's-complement nibbles** two-per-byte along the last
  (output/H) axis — column ``j`` and column ``j + ceil(H/2)`` share a byte
  — padding odd H; ``unpack_int4(pack_int4(q), H) == q`` exactly (pinned by
  ``tests/test_quantize.py``).
* Biases are never quantized — they enter the gate sums in fp32 on every
  path already.

``precision`` values (the knob threaded ``ops`` → ``rnn.run_stack`` →
``classifier``/``autoencoder`` → ``StreamingEngine``):
``None`` (native dtypes, the pre-quantization behavior), ``"fp32"``,
``"bf16"`` (pure cast), ``"int8"``, ``"int4"`` (quantized weights over
bf16 activations).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: The serving-precision axis.  ``None`` (not listed) means "leave dtypes
#: alone" — the default for every existing caller.
PRECISIONS = ("fp32", "bf16", "int8", "int4")

#: Weight storage bits per precision (fp32/bf16 are plain casts).
WEIGHT_BITS = {"fp32": 32, "bf16": 16, "int8": 8, "int4": 4}

#: Symmetric integer range: qmax = 2^(bits-1) - 1 (the -2^(bits-1) code is
#: unused, keeping the grid symmetric around 0).
QMAX = {8: 127, 4: 7}

#: Precisions whose weights are integer-quantized (vs plain casts).
QUANTIZED = ("int8", "int4")


def check_precision(precision) -> None:
    if precision is not None and precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS} or None, "
                         f"got {precision!r}")


def activation_dtype(precision, default):
    """The activation/carry dtype a precision runs with.

    fp32 computes in fp32; bf16/int8/int4 all run bf16 activations (the
    quantized weights dequantize into bf16 registers); ``None`` keeps the
    caller's native dtype.
    """
    if precision is None:
        return default
    check_precision(precision)
    return jnp.float32 if precision == "fp32" else jnp.bfloat16


def quantize(w: jax.Array, bits: int, *, axis: int):
    """Symmetric per-output-channel quantization of ``w`` along ``axis``.

    ``axis`` is the contraction dim (reduced away by the matmul); every
    other coordinate is an output channel with its own scale.  Returns
    ``(q int8, scale fp32)`` with ``scale.shape = w.shape`` minus ``axis``.
    Zero/constant-zero channels get scale 1.0 (their q is 0 anyway), so no
    division ever sees 0.
    """
    qmax = QMAX[bits]
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.round(w / jnp.expand_dims(scale, axis))
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array, *, axis: int) -> jax.Array:
    """The canonical dequant: ``q * scale`` broadcast over ``axis``, fp32.

    Every backend funnels through this one expression (the kernels call it
    on their VMEM-resident refs' values, wrappers and the reference on
    arrays) — the bit-identity contract across backends hinges on it.
    """
    return q.astype(jnp.float32) * jnp.expand_dims(scale, axis)


def fake_quant(w: jax.Array, precision: str, *, axis: int, act_dtype):
    """Quantize→dequantize in one step (reference / step-backend path).

    For the cast precisions this is just ``astype(act_dtype)``; for the
    quantized ones it produces exactly the values the sequence kernel
    dequantizes in-register — same (q, scale), same canonical dequant.
    """
    if precision in QUANTIZED:
        q, s = quantize(w, WEIGHT_BITS[precision], axis=axis)
        return dequantize(q, s, axis=axis).astype(act_dtype)
    return w.astype(act_dtype)


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int4 codes two-per-byte along the last axis (pad odd lengths).

    ``q`` holds values in [-7, 7] (int8); the result is uint8 of length
    ``m = ceil(H/2)``: byte ``j`` holds column ``j`` in its low nibble and
    column ``j + m`` in its high nibble (two's-complement nibbles — ``-3``
    stores as ``0xD``).  Splitting the axis in halves, rather than
    interleaving even and odd columns, lets a kernel unpack with one
    lane-axis concatenation, which Mosaic lowers; an interleave needs a
    minor-dim reshape that it refuses.
    """
    if q.shape[-1] % 2:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, 1)])
    m = q.shape[-1] // 2
    u = q.astype(jnp.uint8)
    return (u[..., :m] & 0xF) | ((u[..., m:] & 0xF) << 4)


def _int4_codes(packed: jax.Array, n: int) -> jax.Array:
    """The int32 codes of :func:`pack_int4` output (kernel-safe arithmetic)."""
    p = packed.astype(jnp.int32)
    nib = jnp.concatenate([p & 0xF, (p >> 4) & 0xF], axis=-1)[..., :n]
    return jnp.where(nib >= 8, nib - 16, nib)


def unpack_int4(packed: jax.Array, n: int) -> jax.Array:
    """Invert :func:`pack_int4`: ``[..., ceil(n/2)] uint8 → [..., n] int8``.

    Sign-extends each nibble, rejoins the low and high halves and drops the
    pad column when ``n`` is odd.
    """
    return _int4_codes(packed, n).astype(jnp.int8)


def packed_weight(q: jax.Array, bits: int) -> jax.Array:
    """Storage form of a quantized weight: int8 as-is, int4 nibble-packed."""
    return pack_int4(q) if bits == 4 else q


def kernel_weight(w_ref_val: jax.Array, scale: jax.Array, bits: int, *,
                  hidden: int, act_dtype) -> jax.Array:
    """In-register dequant of a VMEM-resident quantized weight operand.

    ``w_ref_val``: the kernel's weight block — ``[D, G, H]`` int8, or
    ``[D, G, ceil(H/2)]`` uint8 when int4-packed.  ``scale``: ``[G, H]``
    fp32.  Returns the ``[D, G, H]`` activation-dtype weights the gate
    matmuls consume — exactly :func:`fake_quant`'s values.
    """
    q = _int4_codes(w_ref_val, hidden) if bits == 4 else w_ref_val
    return dequantize(q, scale, axis=0).astype(act_dtype)


def weight_bytes(in_dim: int, hidden: int, gates: int, precision) -> int:
    """Resident weight bytes for one layer at a precision (VMEM budget math).

    ``wx [I, G, H]`` + ``wh [H, G, H]`` at the storage bit-width, plus the
    two fp32 ``[G, H]`` scale tensors for the quantized precisions, plus
    the fp32 bias.  ``None`` prices as fp32 (native dtypes).
    """
    bits = WEIGHT_BITS.get(precision, 32)
    total = (in_dim + hidden) * gates * hidden * bits // 8
    if precision in QUANTIZED:
        total += 2 * gates * hidden * 4          # per-channel fp32 scales
    total += gates * hidden * 4                  # fp32 bias
    return total
