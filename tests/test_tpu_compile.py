"""The sequence kernels compile natively for a TPU v5e chip.

Interpret mode accepts block shapes and layouts that the TPU compiler
refuses, so these tests compile the serving kernels (``interpret=False``)
against a *described* ``v5e:2x2`` topology — no chip attached, shapes only —
at the paper's serving launch (64 sessions × S=30 chains = 1920 rows, one
140-step beat, I=1, H=8) and at the scheduler's wide top rung (T=512,
I=128, H=256), in the serving form: carried state in, per-row lengths.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

PAPER = (1920, 140, 1, 8)        # (B, T, I, H)
WIDE = (256, 512, 128, 256)

CASES = [("lstm", PAPER, "fp32"), ("lstm", PAPER, "bf16"),
         ("lstm", PAPER, "int8"), ("lstm", WIDE, "fp32"),
         ("lstm", WIDE, "int4"),
         ("gru", PAPER, "fp32"), ("gru", PAPER, "bf16"),
         ("gru", PAPER, "int8"), ("gru", WIDE, "fp32"),
         ("gru", WIDE, "int4")]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _operands(cell, shape, precision, dev):
    """ShapeDtypeStructs for ``ops.fused_*_seq`` in kernel weight layout:
    positional operands, keyword operands, and the static ``weight_bits``."""
    B, T, I, H = shape
    G = 4 if cell == "lstm" else 3

    def s(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=dev)

    act = jnp.float32 if precision == "fp32" else jnp.bfloat16
    kw, bits = {}, None
    if precision in ("fp32", "bf16"):
        wx, wh = s((I, G, H), act), s((H, G, H), act)
    else:
        bits = 8 if precision == "int8" else 4
        width = H if bits == 8 else -(-H // 2)
        code = jnp.int8 if bits == 8 else jnp.uint8
        wx, wh = s((I, G, width), code), s((H, G, width), code)
        kw = dict(wx_scale=s((G, H), jnp.float32),
                  wh_scale=s((G, H), jnp.float32))
    kw["h0"] = s((B, H), act)
    if cell == "lstm":
        kw["c0"] = s((B, H), jnp.float32)
    args = (wx, wh, s((G, H), jnp.float32), s((B, T, I), act),
            s((B,), jnp.uint32), s((B,), jnp.int32))
    return args, kw, bits


@pytest.mark.parametrize("cell,shape,precision", CASES)
def test_seq_kernel_compiles_for_v5e(one_chip, cell, shape, precision):
    args, kw, bits = _operands(cell, shape, precision, one_chip)
    fn = ops.fused_lstm_seq if cell == "lstm" else ops.fused_gru_seq

    def launch(wx, wh, b, x, rows, lengths, kw):
        return fn(wx, wh, b, x, rows, 0, 0, 0.125, lengths=lengths,
                  weight_bits=bits, interpret=False, **kw)

    compiled = jax.jit(launch).lower(*args, kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
