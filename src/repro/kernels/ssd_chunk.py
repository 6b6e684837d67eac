"""Pallas TPU kernel: fused Mamba2/SSD chunk scan.

EXPERIMENTS.md §Perf Cell B found the jnp SSD memory-bound on its fp32
intermediates (dtx, decay, y_intra are materialized per chunk ×72 layers).
This kernel is the identified fix: the whole chunk pipeline — cumulative
log-decays, intra-chunk (quadratic) attention-like term, inter-chunk state
recurrence — runs in VMEM per (batch, head-block), streaming x/dt/B/C blocks
from HBM exactly once and carrying the [bh, P, N] state in scratch across the
sequential chunk dimension.  n_groups=1 (the assigned mamba2/jamba configs).

Grid: (B, H/bh, L/Q) with the chunk axis "arbitrary" (sequential).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
            state_ref, *, q_chunk: int, grid_c: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # [Q, bh, P]
    dt = dt_ref[0].astype(jnp.float32)        # [Q, bh]
    a = a_ref[0].astype(jnp.float32)          # [bh]
    bm = b_ref[0].astype(jnp.float32)         # [Q, N]
    cm = c_ref[0].astype(jnp.float32)         # [Q, N]
    d_skip = d_ref[0].astype(jnp.float32)     # [bh]

    l = dt * a[None, :]                       # [Q, bh] log-decay per step
    cs = jnp.cumsum(l, axis=0)                # inclusive
    dtx = dt[..., None] * x                   # [Q, bh, P]

    # --- intra-chunk quadratic term ------------------------------------
    scores = jnp.einsum("qn,kn->qk", cm, bm)                  # [Q, Q]
    decay = jnp.exp(cs[:, None, :] - cs[None, :, :])          # [Q, Q, bh]
    tri = jax.lax.broadcasted_iota(jnp.int32, (q_chunk, q_chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q_chunk, q_chunk), 1)
    gate = jnp.where(tri[..., None], decay, 0.0)              # [Q, Q, bh]
    y = jnp.einsum("qk,qkh,khp->qhp", scores, gate, dtx)

    # --- inter-chunk contribution from carried state --------------------
    state = state_ref[...]                                    # [bh, P, N]
    cin = jnp.exp(cs)                                         # [Q, bh]
    y += jnp.einsum("qn,qh,hpn->qhp", cm, cin, state)

    # --- state update ----------------------------------------------------
    dec_end = jnp.exp(cs[-1:, :] - cs)                        # [Q, bh]
    new_state = state * jnp.exp(cs[-1])[:, None, None] \
        + jnp.einsum("qn,qh,qhp->hpn", bm, dec_end, dtx)
    state_ref[...] = new_state

    y_ref[0] = (y + d_skip[None, :, None] * x).astype(y_ref.dtype)

    @pl.when(c_idx == grid_c - 1)
    def _store_state():
        hout_ref[0] = new_state.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_chunk", "block_h",
                                             "interpret"))
def ssd_chunk_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
                   cm: jax.Array, d_skip: jax.Array, *, q_chunk: int = 256,
                   block_h: int = 8, interpret: bool | None = None):
    """Fused SSD scan (n_groups=1).

    x: [B, L, H, P]; dt: [B, L, H] (post-softplus); a: [H] (negative);
    bm, cm: [B, L, N]; d_skip: [H].
    Returns (y [B, L, H, P], final state [B, H, P, N] fp32).
    """
    B, L, H, P = x.shape
    N = bm.shape[-1]
    q = min(q_chunk, L)
    while L % q:
        q -= 1
    bh = min(block_h, H)
    while H % bh:
        bh -= 1
    grid = (B, H // bh, L // q)
    a2 = jnp.asarray(a).reshape(1, H)
    d2 = jnp.asarray(d_skip).reshape(1, H)
    y, h_final = pl.pallas_call(
        functools.partial(_kernel, q_chunk=q, grid_c=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, bh, P), lambda b, h, c: (b, c, h, 0)),  # x
            pl.BlockSpec((1, q, bh), lambda b, h, c: (b, c, h)),        # dt
            pl.BlockSpec((1, bh), lambda b, h, c: (0, h)),              # a
            pl.BlockSpec((1, q, N), lambda b, h, c: (b, c, 0)),         # B
            pl.BlockSpec((1, q, N), lambda b, h, c: (b, c, 0)),         # C
            pl.BlockSpec((1, bh), lambda b, h, c: (0, h)),              # D
        ],
        out_specs=[
            pl.BlockSpec((1, q, bh, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, bh, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(x, dt, a2, bm, cm, d2)
    return y, h_final
