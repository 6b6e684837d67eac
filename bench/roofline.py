"""Peaks of the chips the benchmark runs on, and the kernels' work by shape.

Peaks of one TPU v5e chip, from Google Cloud's "TPU v5e" documentation:
197 TFLOP/s in bfloat16 and 819 GB/s of HBM bandwidth.  No float32 MXU
rate is published; the float32 kernels are held against the bfloat16 peak,
which can only make their share smaller.  A device kind that is not in the
table is an error, never a default.

The work of one launch of the sequence-fused LSTM kernel
(``kernels/mcd_lstm_seq.py``) over ``rows`` chains and ``T`` steps, with
input width ``I``, hidden width ``H`` and ``G`` gates, at 4 bytes a value:

* FLOPs: ``2 * rows * T * G * (I + H) * H`` (one matmul per gate on each
  side); the elementwise gate math and the in-kernel mask hash are not
  counted, so the share is of the matrix units' roofline;
* bytes: the input sequence ``rows*T*I`` and the output sequence
  ``rows*T*H`` read and written once, the weights ``G*(I+H)*H + G*H``, the
  carry in and out ``4*rows*H`` and the row ids and lengths ``2*rows``.
"""

from __future__ import annotations

PEAKS = {
    # device_kind: (peak FLOP/s, HBM bytes/s)
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}

BYTES = 4


def peaks(device_kind: str) -> tuple[float, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None


def lstm_launch_flops(rows: int, T: int, I: int, H: int, G: int = 4) -> int:
    return 2 * rows * T * G * (I + H) * H


def lstm_launch_bytes(rows: int, T: int, I: int, H: int, G: int = 4) -> int:
    values = (rows * T * I + rows * T * H + G * (I + H) * H + G * H
              + 4 * rows * H + 2 * rows)
    return BYTES * values


def least_time(flops: float, nbytes: float, device_kind: str):
    """``(seconds, bound)``: the larger of compute and memory time."""
    peak_flops, bw = peaks(device_kind)
    t_c, t_m = flops / peak_flops, nbytes / bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def gates(cfg: dict) -> int:
    return {"lstm": 4, "gru": 3}[cfg["cell"]]
