"""Property tests for the quantization module (ISSUE 6 satellite).

Hypothesis-driven (real hypothesis when installed, the deterministic
conftest stub otherwise): round-trip error bounds per channel, degenerate
zero/constant channels, odd-width int4 packing, and layout invariance —
the algebraic facts the cross-backend bit-identity suite builds on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import quantize


def _weights(key, i, g, h, scale=1.0):
    return jax.random.normal(jax.random.key(key), (i, g, h)) * scale


class TestRoundTrip:
    @settings(max_examples=15, deadline=None)
    @given(bits=st.sampled_from([8, 4]),
           i=st.integers(1, 24), h=st.integers(1, 24),
           key=st.integers(0, 2**16), amp=st.floats(1e-3, 100.0))
    def test_error_bounded_per_channel(self, bits, i, h, key, amp):
        """|w - deq(q)| <= scale/2 per element: round() lands on the nearest
        grid point and |w| <= amax = qmax*scale keeps clip() inactive."""
        w = np.asarray(_weights(key, i, 4, h, amp), np.float64)
        q, s = quantize.quantize(jnp.asarray(w, jnp.float32), bits, axis=0)
        deq = np.asarray(quantize.dequantize(q, s, axis=0), np.float64)
        bound = np.asarray(s, np.float64)[None] / 2 + 1e-6 * amp
        assert (np.abs(w - deq) <= bound).all()

    @settings(max_examples=10, deadline=None)
    @given(bits=st.sampled_from([8, 4]), key=st.integers(0, 2**16))
    def test_codes_within_symmetric_range(self, bits, key):
        q, _ = quantize.quantize(_weights(key, 8, 4, 8), bits, axis=0)
        qmax = quantize.QMAX[bits]
        qn = np.asarray(q)
        assert qn.dtype == np.int8
        assert qn.min() >= -qmax and qn.max() <= qmax

    @settings(max_examples=10, deadline=None)
    @given(bits=st.sampled_from([8, 4]), key=st.integers(0, 2**16),
           h=st.integers(1, 16))
    def test_layout_invariance(self, bits, key, h):
        """Kernel layout [I, G, H] axis=0 and core layout [G, I, H] axis=1
        give bit-identical (q, scale) — run_stack's reference path relies
        on this to fake-quant without re-layouting."""
        w = _weights(key, 12, 4, h)                     # [I, G, H]
        qk, sk = quantize.quantize(w, bits, axis=0)
        qc, sc = quantize.quantize(jnp.moveaxis(w, 0, 1), bits, axis=1)
        np.testing.assert_array_equal(np.asarray(qk),
                                      np.asarray(jnp.moveaxis(qc, 1, 0)))
        np.testing.assert_array_equal(np.asarray(sk), np.asarray(sc))


class TestDegenerateChannels:
    def test_zero_channel_scale_one_codes_zero(self):
        w = jnp.zeros((6, 4, 5))
        q, s = quantize.quantize(w, 8, axis=0)
        np.testing.assert_array_equal(np.asarray(q), 0)
        np.testing.assert_array_equal(np.asarray(s), 1.0)
        np.testing.assert_array_equal(
            np.asarray(quantize.dequantize(q, s, axis=0)), 0.0)

    def test_constant_channel_exact(self):
        """A channel whose elements all equal ±amax round-trips exactly."""
        w = jnp.full((6, 4, 5), 0.375)
        q, s = quantize.quantize(w, 4, axis=0)
        np.testing.assert_array_equal(np.asarray(q), quantize.QMAX[4])
        deq = quantize.dequantize(q, s, axis=0)
        np.testing.assert_allclose(np.asarray(deq), 0.375, rtol=1e-7)

    def test_mixed_zero_and_live_channels(self):
        w = np.zeros((6, 1, 3), np.float32)
        w[:, 0, 1] = np.linspace(-1, 1, 6)
        q, s = quantize.quantize(jnp.asarray(w), 8, axis=0)
        sn = np.asarray(s)
        assert sn[0, 0] == 1.0 and sn[0, 2] == 1.0
        assert sn[0, 1] == pytest.approx(1.0 / 127)


class TestInt4Packing:
    @settings(max_examples=15, deadline=None)
    @given(h=st.integers(1, 33), key=st.integers(0, 2**16))
    def test_pack_unpack_roundtrip_any_width(self, h, key):
        """Exact for every H, odd widths included (pad nibble dropped)."""
        q, _ = quantize.quantize(_weights(key, 5, 4, h), 4, axis=0)
        packed = quantize.pack_int4(q)
        assert packed.shape == (5, 4, (h + 1) // 2)
        assert packed.dtype == jnp.uint8
        np.testing.assert_array_equal(
            np.asarray(quantize.unpack_int4(packed, h)), np.asarray(q))

    def test_every_code_exact(self):
        """All 15 legal int4 codes survive the nibble round-trip."""
        q = jnp.arange(-7, 8, dtype=jnp.int8).reshape(1, -1)
        np.testing.assert_array_equal(
            np.asarray(quantize.unpack_int4(quantize.pack_int4(q), 15)),
            np.asarray(q))

    def test_packed_weight_dispatch(self):
        q = jnp.ones((4, 4, 6), jnp.int8)
        assert quantize.packed_weight(q, 8) is q
        assert quantize.packed_weight(q, 4).shape == (4, 4, 3)


class TestKnobPlumbing:
    def test_check_precision(self):
        for p in quantize.PRECISIONS + (None,):
            quantize.check_precision(p)
        with pytest.raises(ValueError, match="precision"):
            quantize.check_precision("fp16")

    def test_activation_dtype(self):
        assert quantize.activation_dtype(None, jnp.float16) == jnp.float16
        assert quantize.activation_dtype("fp32", jnp.bfloat16) == jnp.float32
        for p in ("bf16", "int8", "int4"):
            assert quantize.activation_dtype(p, jnp.float32) == jnp.bfloat16

    def test_weight_bytes_monotonic(self):
        sizes = [quantize.weight_bytes(16, 32, 4, p)
                 for p in (None, "fp32", "bf16", "int8", "int4")]
        assert sizes[0] == sizes[1]                  # None prices as fp32
        assert sizes[1] > sizes[2] > sizes[3] > sizes[4]
        # bf16 halves fp32 exactly (no scales); int8 adds scale rows
        assert sizes[2] - quantize.weight_bytes(16, 32, 4, "int8") \
            == (16 + 32) * 4 * 32 * 1 - 2 * 4 * 32 * 4

    def test_kernel_weight_matches_fake_quant(self):
        """The in-kernel dequant == the wrapper-level oracle, both widths."""
        w = _weights(3, 10, 4, 7)
        for precision, bits in (("int8", 8), ("int4", 4)):
            q, s = quantize.quantize(w, bits, axis=0)
            got = quantize.kernel_weight(
                quantize.packed_weight(q, bits), s, bits, hidden=7,
                act_dtype=jnp.bfloat16)
            want = quantize.fake_quant(w, precision, axis=0,
                                       act_dtype=jnp.bfloat16)
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
