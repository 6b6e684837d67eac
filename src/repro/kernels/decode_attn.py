"""Pallas TPU kernel: fused single-token (decode) attention over a KV cache.

The hot spot of Bayesian serving (EXPERIMENTS.md §Perf Cell C): one query
token attends over a seq_len-sized cache.  The kernel streams cache blocks
HBM→VMEM once, keeping the online-softmax running (max, denom, acc) in VMEM
scratch — no score tensor, no cache round-trips, and GQA handled by grouping
query heads with their KV head.

Grid: (batch, seq_blocks); the seq dimension is "arbitrary" (sequential) so
scratch carries across blocks; positions beyond `pos` are masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_s: int, grid_s: int, kv_heads: int, rep: int, hd: int):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0, 0]
    q = q_ref[0].reshape(kv_heads, rep, hd).astype(jnp.float32)   # [KV,rep,hd]
    k = k_ref[0].astype(jnp.float32)                              # [bs,KV,hd]
    v = v_ref[0].astype(jnp.float32)
    scale = hd ** -0.5
    s = jnp.einsum("grh,sgh->grs", q, k) * scale                  # [KV,rep,bs]
    j = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, rep, block_s), 2) \
        + s_idx * block_s
    s = jnp.where(j <= pos, s, -jnp.inf)

    m_prev = m_ref[...]                                           # [KV,rep]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[..., None] \
        + jnp.einsum("grs,sgh->grh", p, v)

    @pl.when(s_idx == grid_s - 1)
    def _store():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(kv_heads * rep, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, block_s: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """q: [B, H, hd] (post-RoPE); caches: [B, S, KV, hd]; pos: scalar.

    Returns [B, H, hd] attention output (softmax over positions ≤ pos).
    """
    B, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    bs = min(block_s, S)
    while S % bs:
        bs -= 1
    grid = (B, S // bs)
    pos2 = jnp.asarray(pos, jnp.int32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, block_s=bs, grid_s=grid[1], kv_heads=KV,
                          rep=rep, hd=hd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, s: (0, 0)),           # pos
            pl.BlockSpec((1, H, hd), lambda b, s: (b, 0, 0)),    # q
            pl.BlockSpec((1, bs, KV, hd), lambda b, s: (b, s, 0, 0)),
            pl.BlockSpec((1, bs, KV, hd), lambda b, s: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, s: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((KV, rep), jnp.float32),
            pltpu.VMEM((KV, rep), jnp.float32),
            pltpu.VMEM((KV, rep, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(pos2, q, k_cache, v_cache)
