"""Pallas TPU kernel: fused MCD-mask + matmul (DX unit feeding the MVM).

y = (x ⊙ z / (1-p)) @ W, with z generated in VMEM per x-tile from the counter
PRNG — the masked operand never exists in HBM.  K-tiled with an fp32 VMEM
accumulator; MXU dims default to 128/256 multiples.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary"), accumulate in scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import prng
from repro.kernels import resolve_interpret


def _kernel(rows_ref, key_ref, x_ref, w_ref, o_ref, acc_ref, *,
            p_drop: float, k_dim: int, block_k: int, grid_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if p_drop > 0.0:
        rows = rows_ref[...][:, 0]
        key = key_ref[0, 0]
        cols = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1) \
            + k.astype(jnp.uint32) * jnp.uint32(block_k)
        idx = rows[:, None].astype(jnp.uint32) * jnp.uint32(k_dim) + cols
        bits = prng._mix32(key ^ prng._mix32(idx))
        keep = bits >= prng.bernoulli_keep_threshold(p_drop)
        scale = jnp.asarray(1.0 / (1.0 - p_drop), x.dtype)
        x = jnp.where(keep, x * scale, jnp.zeros_like(x))
    acc_ref[...] += jnp.dot(x, w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == grid_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("p_drop", "block_m", "block_n",
                                             "block_k", "interpret"))
def mcd_matmul(x: jax.Array, w: jax.Array, rows: jax.Array, key: jax.Array,
               p_drop: float, *, block_m: int = 256, block_n: int = 256,
               block_k: int = 512, interpret: bool | None = None) -> jax.Array:
    """x: [M, K], w: [K, N], rows: [M] → [M, N] (fp32-accumulated)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, bm, N, bn, K, bk)
    grid = (M // bm, N // bn, K // bk)
    rows2 = rows.astype(jnp.int32).reshape(M, 1)
    key2 = jnp.asarray(key, jnp.uint32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, p_drop=p_drop, k_dim=K, block_k=bk,
                          grid_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(rows2, key2, x, w)
