"""Pallas TPU kernel: Bernoulli mask generation + apply (the LFSR + DX unit).

The paper's Fig. 3 sampler (LFSR → SIPO → FIFO) plus the DX masking unit of
Fig. 2, fused: random bits are produced *in VMEM* by the counter-PRNG
(~10 uint32 VPU ops/lane), thresholded to a Bernoulli(p) keep-mask, applied
to the activation tile, and never written to HBM.  Generation cost hides
under the surrounding compute exactly as the paper's Fig. 4 overlap.

Mask semantics match :func:`repro.core.mcd.feature_mask` bit-for-bit: element
(b, f) draws from stream index ``rows[b]·n_feat + f`` under the site key —
identical regardless of tiling, sharding, or restart.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import prng
from repro.kernels import resolve_interpret


def _kernel(rows_ref, key_ref, x_ref, o_ref, *, p_drop: float, n_feat: int,
            block_f: int):
    j = pl.program_id(1)
    rows = rows_ref[...][:, 0]                      # [bb]
    key = key_ref[0, 0]
    cols = jax.lax.broadcasted_iota(jnp.uint32, x_ref.shape, 1) \
        + jnp.uint32(j * block_f)
    idx = rows[:, None].astype(jnp.uint32) * jnp.uint32(n_feat) + cols
    bits = prng._mix32(key ^ prng._mix32(idx))
    keep = bits >= prng.bernoulli_keep_threshold(p_drop)
    scale = jnp.asarray(1.0 / (1.0 - p_drop), x_ref.dtype)
    o_ref[...] = jnp.where(keep, x_ref[...] * scale, jnp.zeros_like(x_ref[...]))


@functools.partial(jax.jit, static_argnames=("p_drop", "block_b", "block_f",
                                             "interpret"))
def masked_activation(x: jax.Array, rows: jax.Array, key: jax.Array,
                      p_drop: float, *, block_b: int = 256,
                      block_f: int = 512,
                      interpret: bool | None = None) -> jax.Array:
    """x: [B, F] activations → x ⊙ z / (1-p) with z ~ Bern(1-p) per (row, f)."""
    B, F = x.shape
    bb, bf = min(block_b, B), min(block_f, F)
    assert B % bb == 0 and F % bf == 0, (B, bb, F, bf)
    rows2 = rows.astype(jnp.int32).reshape(B, 1)
    key2 = jnp.asarray(key, jnp.uint32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, p_drop=p_drop, n_feat=F, block_f=bf),
        grid=(B // bb, F // bf),
        in_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bb, bf), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, F), x.dtype),
        interpret=resolve_interpret(interpret),
    )(rows2, key2, x)
