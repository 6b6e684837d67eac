"""Roofline analysis from compiled artifacts (no hardware required).

Terms (per device, seconds), with the peaks of the device the program was
compiled for (:data:`PEAKS`, keyed by ``jax.Device.device_kind``):
  compute    = HLO_FLOPs / peak bf16 FLOP/s
  memory     = HLO_bytes / HBM bandwidth
  collective = collective_bytes / ICI bandwidth per link

``cost_analysis()`` on the SPMD-partitioned module reports *per-device*
flops/bytes.  collective_bytes is parsed from the partitioned HLO text:
per-op output bytes × an op factor (all-reduce counts 2× for the
reduce+broadcast ring phases; others 1×).
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Per-chip roofline peaks."""

    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # ICI bytes/s per link


#: Device kind (as ``jax.devices()[0].device_kind`` reports it) → peaks.
#: TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip
#: (four links, 50 GB/s each).
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: The device the analytic models (``repro.dse.tpu_model``) and the
#: production-mesh dry runs price: the chip this repo serves on.
MODELED_DEVICE = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a device not in :data:`PEAKS` raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no roofline peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_COLL_RE = re.compile(
    r"=\s*([a-z0-9_]+)\[([0-9,]*)\]"                  # dtype[shape]
    r"(?:\{[^}]*\})?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_TUPLE_COLL_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_FACTORS = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
            "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(dtype: str, dims: str) -> float:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return float(n * nb)


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-op-kind collective traffic (bytes, per device) from HLO text."""
    out: dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        out[kind] = out.get(kind, 0.0) + _shape_bytes(dtype, dims)
    for m in _TUPLE_COLL_RE.finditer(hlo_text):
        tup, kind = m.group(1), m.group(2)
        total = 0.0
        for part in re.finditer(r"([a-z0-9_]+)\[([0-9,]*)\]", tup):
            total += _shape_bytes(part.group(1), part.group(2))
        out[kind] = out.get(kind, 0.0) + total / 2.0  # tuple lists in+out
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    bytes_hbm: float             # per device
    bytes_collective: float      # per device (factor-weighted)
    coll_by_kind: dict
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    memory_per_device: dict      # from memory_analysis()

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def roofline_fraction(self) -> float:
        """compute term / binding term — 1.0 means compute-bound at peak."""
        return self.t_compute / max(self.t_bound, 1e-30)


def analyse(compiled, device_kind: str,
            hlo_text: str | None = None) -> Roofline:
    """Roofline terms of ``compiled`` on a ``device_kind`` chip."""
    pk = peaks(device_kind)
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    bytes_hbm = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    weighted = sum(_FACTORS[k] * v for k, v in coll.items())
    t_c = flops / pk.flops
    t_m = bytes_hbm / pk.hbm_bw
    t_x = weighted / pk.ici_bw
    bott = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    return Roofline(flops=flops, bytes_hbm=bytes_hbm,
                    bytes_collective=weighted, coll_by_kind=coll,
                    t_compute=t_c, t_memory=t_m, t_collective=t_x,
                    bottleneck=bott, memory_per_device=mem)


def model_flops(cfg, cell, chips: int) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE), per device.

    N counts *active* parameters (MoE: top-k experts + shared); D = tokens
    processed by the step (train: batch·seq fwd+bwd = 6ND; prefill: 2ND;
    decode: 2N per token · batch).
    """
    n_active = active_params(cfg)
    if cell.kind == "train":
        d = cell.global_batch * cell.seq_len
        total = 6.0 * n_active * d
    elif cell.kind == "prefill":
        d = cell.global_batch * cell.seq_len
        total = 2.0 * n_active * d
    else:  # decode: one token per sequence
        total = 2.0 * n_active * cell.global_batch
    return total / chips


def active_params(cfg) -> float:
    """Active parameter count from the architecture config (no allocation)."""
    from repro.models import backbone as bb
    from repro.models import mamba2 as m2
    D = cfg.d_model
    hd = cfg.head_dim
    n = cfg.vocab_size * D * (1 if cfg.tie_embeddings else 2)
    def block_params(kind: str) -> float:
        mixer, cross, ffn = bb._parse(kind)
        p = 0.0
        if mixer in ("attn", "enc_attn", "dec_attn"):
            p += D * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)
        elif mixer == "mla":
            c = cfg.mla
            p += D * cfg.num_heads * (c.nope_head_dim + c.rope_head_dim)
            p += D * (c.kv_lora_rank + c.rope_head_dim)
            p += c.kv_lora_rank * cfg.num_heads * (c.nope_head_dim + c.v_head_dim)
            p += cfg.num_heads * c.v_head_dim * D
        elif mixer == "mamba":
            d_inner, n_heads, conv_dim = m2.dims(D, cfg.ssm)
            d_in_proj = 2 * d_inner + 2 * cfg.ssm.n_groups * cfg.ssm.d_state + n_heads
            p += D * d_in_proj + d_inner * D + conv_dim * cfg.ssm.d_conv
        if cross:
            p += D * hd * (cfg.num_heads * 2 + cfg.num_kv_heads * 2)
        if ffn == "mlp":
            p += 3 * D * cfg.d_ff
        elif ffn == "moe":
            mo = cfg.moe
            p += 3 * D * mo.d_ff_expert * (mo.top_k + mo.num_shared)
            p += D * mo.num_experts        # router
        return p
    for stage in tuple(cfg.stages) + tuple(cfg.encoder_stages):
        for kind in stage.pattern:
            n += stage.repeat * block_params(kind)
    return n
