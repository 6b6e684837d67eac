"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel <name>.py carries explicit BlockSpec VMEM tiling; ops.py holds
the jit'd wrappers; ref.py the pure-jnp oracles the tests assert against.
Every kernel's ``interpret`` defaults to :func:`resolve_interpret`: native
lowering on a TPU backend, the Pallas interpreter anywhere else (CPU tests).

  bernoulli_mask  counter-PRNG mask generate+apply (the paper's LFSR + DX)
  mcd_matmul      fused MCD mask + matmul (K-tiled, fp32 VMEM accumulator)
  mcd_lstm        fused Bayesian LSTM cell step (the paper's Fig. 2 datapath)
  mcd_lstm_seq    sequence-fused Bayesian LSTM layer — weights VMEM-resident
                  across all T timesteps (the paper's Fig. 5 wave pipelining)
  decode_attn     flash-decode attention over the KV cache (serving hot path)
  ssd_chunk       fused Mamba2/SSD chunk scan (VMEM-resident chunk state)
  quantize        per-channel int8/int4 weight quantization for the serving
                  path — packed codes + scales dequantized in-register by
                  the sequence kernels (the ``precision`` knob)

ops.py exposes the ``LSTM_BACKENDS`` dispatch consumed by
``repro.core.rnn.run_stack``.
"""

from __future__ import annotations


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel's ``interpret`` argument: None interprets unless JAX's
    default backend is a TPU.

    On a TPU the kernels lower natively unless a caller asks for the
    interpreter by name, so no path there interprets silently.
    """
    if interpret is None:
        import jax
        return jax.default_backend() != "tpu"
    return bool(interpret)
