"""TPU-side analytic performance model — the roofline replaces DSPs/II.

Napkin-math formulas per block kind (flops, HBM bytes, collective bytes per
device) as a function of the architecture config and the hardware
configuration (mesh split, microbatches, fsdp, remat).  The same three-term
roofline as :mod:`repro.launch.analysis` — validated against the probe-based
measurements in EXPERIMENTS.md §Roofline (this model is the cheap inner loop
of the DSE; the probes are the ground truth).

Hardware knobs here = the paper's reuse factors: they trade parallelism
(lower latency) against per-chip residency (HBM instead of DSPs).
"""

from __future__ import annotations

import dataclasses

from repro.dse.fpga_model import RNNArch
from repro.launch import analysis
from repro.launch.analysis import active_params
from repro.models.config import ArchConfig, ShapeCell


#: The modeled chip's peaks.  The model prices that chip whatever device
#: the process runs on; ``repro.dse.calibrate`` maps its seconds onto the
#: ticks the engine actually observes.
_PEAKS = analysis.peaks(analysis.MODELED_DEVICE)


@dataclasses.dataclass(frozen=True)
class TpuHwConfig:
    """Hardware half of the DSE space (TPU analogue of R_x/R_h/R_d)."""
    data: int = 16
    model: int = 16
    pod: int = 1
    microbatches: int = 1
    fsdp: bool = False
    remat: bool = True

    @property
    def chips(self) -> int:
        return self.data * self.model * self.pod

    @property
    def dp(self) -> int:
        return self.data * self.pod


def rnn_step_model(arch: RNNArch, *, batch: float = 1, n_samples: float = 1,
                   data: int = 1, dtype_bytes: int = 2) -> dict:
    """Roofline terms for the paper's recurrent stack itself (both cells).

    The TPU analogue of §IV-B/§IV-C for the Bayesian RNN workload: per-gate
    flop and byte counts (``arch.gates`` — 4 for LSTM, 3 for GRU, so the
    GRU row prices at 3/4 of the LSTM datapath exactly as in
    ``fpga_model.dsp_usage``), with ``batch × n_samples`` MC-chain rows
    sharded ``data``-ways (`repro.launch.rnn_shardings`' data strategy —
    the mesh split is the reuse-factor analogue here).  ``batch`` and
    ``n_samples`` may be fractional: under early-exit serving the
    controller prices *expected* active chains (ceiling × survival
    ratio), and a roofline is smooth in the row dimension.

    Weight bytes are charged **once per launch**, not per timestep — the
    sequence-fused kernel's VMEM residency (docs/kernels.md) is precisely
    this term's reduction; activations stream per step.

    ``arch.weight_bits`` prices the quantized serving path: ``wx``/``wh``
    store at ``weight_bits/8`` bytes per element plus the fp32 per-channel
    scale rows (2 × G × H × 4, charged only below 16 bits — bf16 carries no
    scales), while the bias and activations stay at ``dtype_bytes``.  At
    the default 16 bits this reduces exactly to the pre-quantization
    formula, so calibrated DSE baselines are unchanged.
    """
    g = float(arch.gates)
    rows = max(batch * n_samples / max(data, 1), 1.0)
    _ = arch.dsp_per_mac                  # validates weight_bits
    w_byte = arch.weight_bits / 8.0
    flops_step = 0.0          # per row per timestep
    weight_bytes = 0.0        # resident per launch, per device
    act_bytes_step = 0.0      # streamed per row per timestep
    for (i_dim, h_dim) in arch.layer_dims():
        flops_step += 2.0 * g * (i_dim * h_dim + h_dim * h_dim)
        flops_step += 12.0 * h_dim                     # elementwise tail
        weight_bytes += g * (i_dim + h_dim) * h_dim * w_byte
        weight_bytes += g * h_dim * dtype_bytes        # bias row
        if arch.weight_bits < 16:
            weight_bytes += 2 * g * h_dim * 4          # fp32 scales (wx, wh)
        act_bytes_step += (i_dim + h_dim) * dtype_bytes
    h_last = arch.layer_dims()[-1][1]
    head_mult = arch.timesteps if arch.kind == "autoencoder" else 1
    flops_head = 2.0 * h_last * arch.output_dim * head_mult
    # NOTE: layer_dims() already spans encoder *and* decoder for the AE, so
    # T is not doubled here — the paper's ×2 is a latency-serialization
    # fact (decoder waits for the encoder), not extra work, and a roofline
    # prices work.  (Doubling it penalized AE candidates ~2× in the DSE.)
    t_steps = arch.timesteps
    flops = rows * (t_steps * flops_step + flops_head)
    bytes_hbm = weight_bytes + rows * t_steps * act_bytes_step
    t_c, t_m = flops / _PEAKS.flops, bytes_hbm / _PEAKS.hbm_bw
    return {"flops": flops, "bytes": bytes_hbm, "coll": 0.0,
            "t_compute": t_c, "t_memory": t_m, "t_collective": 0.0,
            "t_step": max(t_c, t_m)}


def rnn_latency_s(arch: RNNArch, hw=None, batch: int = 1,
                  n_samples: int = 1, *, data: int = 1) -> float:
    """TPU latency estimate with the FPGA model's call signature.

    Drop-in ``latency_model=`` for :func:`repro.dse.search.optimize` —
    pass ``hw_model=None`` alongside it, or TPU-sized archs (H far past
    the ZC706's 900 DSPs) are silently rejected by the default FPGA
    reuse-factor gate before this model ever prices them.  ``hw`` (the
    FPGA reuse factors, or None when the gate is off) is irrelevant on
    TPU and ignored; GRU rows price at their 3-gate cost.
    """
    del hw
    return rnn_step_model(arch, batch=batch, n_samples=n_samples,
                          data=data)["t_step"]


def step_model(cfg: ArchConfig, cell: ShapeCell, hw: TpuHwConfig) -> dict:
    """Analytic per-device (flops, bytes, collective bytes) for one step."""
    n_active = active_params(cfg)
    n_total = _total_params(cfg)
    D = cfg.d_model
    if cell.kind == "train":
        tokens_local = cell.global_batch * cell.seq_len / hw.dp
        flops = 6.0 * n_active * tokens_local
        flops += _attention_flops(cfg, cell.seq_len, cell.global_batch,
                                  causal_factor=2.0, bwd=True) / hw.chips
        if hw.remat:
            flops *= 4.0 / 3.0          # one extra forward
        # bytes: weights (re-read per microbatch) + activation stream + moments
        act = tokens_local * D * 2 * 8 * cfg.num_layers
        weights = n_total * 2 / hw.model / (hw.dp if hw.fsdp else 1)
        bytes_hbm = (weights * 3 * hw.microbatches     # w read fwd+bwd(+remat)
                     + act                             # activations
                     + n_total / hw.model * 16)        # moments r/w fp32
        # collectives: grad reduce (2× params) + TP activation all-reduces
        coll = 2 * n_total * 4 / hw.model / (hw.dp if hw.fsdp else 1)
        coll += 2 * 2 * tokens_local * D * 2 * cfg.num_layers  # 2 AR/layer ×2 ring
        if hw.fsdp:
            coll += n_total * 2 / hw.model * 2          # weight all-gathers
    elif cell.kind == "prefill":
        tokens_local = cell.global_batch * cell.seq_len / hw.dp
        flops = 2.0 * n_active * tokens_local
        flops += _attention_flops(cfg, cell.seq_len, cell.global_batch,
                                  causal_factor=2.0, bwd=False) / hw.chips
        weights = n_total * 2 / hw.model / (hw.dp if hw.fsdp else 1)
        act = tokens_local * D * 2 * 8 * cfg.num_layers
        bytes_hbm = weights + act
        coll = 2 * 2 * tokens_local * D * 2 * cfg.num_layers
    else:  # decode
        bsz = max(cell.global_batch / hw.dp, 1)
        flops = 2.0 * n_active * cell.global_batch / hw.chips
        flops += _decode_attention_flops(cfg, cell.seq_len,
                                         cell.global_batch) / hw.chips
        weights = n_total * 2 / hw.model / (hw.dp if hw.fsdp else 1)
        cache = _cache_bytes(cfg, cell.seq_len) * cell.global_batch / hw.chips
        bytes_hbm = weights + cache
        coll = 2 * bsz * D * 2 * 2 * cfg.num_layers
    t_c, t_m = flops / _PEAKS.flops, bytes_hbm / _PEAKS.hbm_bw
    t_x = coll / _PEAKS.ici_bw
    return {"flops": flops, "bytes": bytes_hbm, "coll": coll,
            "t_compute": t_c, "t_memory": t_m, "t_collective": t_x,
            "t_step": max(t_c, t_m, t_x)}


def memory_model(cfg: ArchConfig, cell: ShapeCell, hw: TpuHwConfig) -> float:
    """Per-device HBM residency (bytes) — the TPU resource model (vs 16 GB)."""
    n_total = _total_params(cfg)
    shard = hw.model * (hw.dp if hw.fsdp else 1)
    mem = n_total * 2 / shard                        # bf16 params
    if cell.kind == "train":
        mem += n_total * 2 / shard                   # grads
        mem += n_total * 8 / (hw.model * hw.dp)      # ZeRO moments fp32
        tokens_local = cell.global_batch * cell.seq_len / hw.dp / hw.microbatches
        per_layer = tokens_local * cfg.d_model * 2
        mem += per_layer * (cfg.num_layers if hw.remat else 8 * cfg.num_layers)
    else:
        mem += _cache_bytes(cfg, cell.seq_len) * cell.global_batch / hw.chips
    return mem


def _total_params(cfg: ArchConfig) -> float:
    """All parameters (MoE: every expert), for memory/weight traffic."""
    n = active_params(cfg)
    if cfg.moe is not None:
        moe_layers = sum(st.repeat for st in cfg.stages
                         for k in st.pattern if k.endswith("moe"))
        act_e = cfg.moe.top_k + cfg.moe.num_shared
        n += moe_layers * 3 * cfg.d_model * cfg.moe.d_ff_expert \
            * (cfg.moe.num_experts - act_e + cfg.moe.num_shared * 0)
    return n


def _attention_layers(cfg: ArchConfig) -> int:
    return sum(st.repeat for st in cfg.stages
               for k in st.pattern if k.split(".")[0] in ("attn", "dec_attn", "mla"))


def _attention_flops(cfg: ArchConfig, seq: int, batch: int, *,
                     causal_factor: float, bwd: bool) -> float:
    """Global score+value flops (full S² blocks; ÷2 if block-skipping)."""
    n_attn = _attention_layers(cfg)
    hd = cfg.head_dim if cfg.mla is None else (cfg.mla.nope_head_dim
                                               + cfg.mla.rope_head_dim)
    per_layer = 2.0 * 2.0 * batch * seq * seq * cfg.num_heads * hd
    if bwd:
        per_layer *= 2.5
    # SSD chunk-quadratic term for mamba mixers
    ssm_layers = sum(st.repeat for st in cfg.stages
                     for k in st.pattern if k.split(".")[0] == "mamba")
    ssd = 0.0
    if ssm_layers and cfg.ssm is not None:
        q = cfg.ssm.chunk
        d_inner = cfg.ssm.expand * cfg.d_model
        ssd = 2.0 * 2.0 * batch * seq * q * (d_inner + cfg.ssm.d_state)
        if bwd:
            ssd *= 2.5
    return per_layer * n_attn + ssd * ssm_layers


def _decode_attention_flops(cfg: ArchConfig, seq: int, batch: int) -> float:
    n_attn = _attention_layers(cfg)
    if cfg.mla is not None:
        per = 2.0 * batch * seq * cfg.num_heads * (cfg.mla.kv_lora_rank * 2)
    else:
        per = 2.0 * 2.0 * batch * seq * cfg.num_heads * cfg.head_dim
    return per * n_attn


def _cache_bytes(cfg: ArchConfig, seq: int) -> float:
    """KV/state bytes per sequence."""
    total = 0.0
    for st in cfg.stages:
        for k in st.pattern:
            mixer = k.split(".")[0]
            if mixer in ("attn", "dec_attn"):
                total += st.repeat * 2 * seq * cfg.num_kv_heads * cfg.head_dim * 2
            elif mixer == "mla":
                total += st.repeat * seq * (cfg.mla.kv_lora_rank
                                            + cfg.mla.rope_head_dim) * 2
            elif mixer == "mamba":
                d_inner = cfg.ssm.expand * cfg.d_model
                n_heads = d_inner // cfg.ssm.head_dim
                total += st.repeat * (n_heads * cfg.ssm.head_dim
                                      * cfg.ssm.d_state * 4)
    return total


def search_hw(cfg: ArchConfig, cell: ShapeCell, *, chips: int = 256,
              hbm_limit: float = 16e9, pod: int = 1) -> list[dict]:
    """Enumerate mesh splits × microbatches; keep feasible, sort by t_step.

    The TPU DSE inner loop: the analogue of scanning reuse factors under the
    DSP budget (§IV-B) — scan mesh factorizations under the HBM budget.
    """
    out = []
    d = 1
    while d <= chips:
        if chips % d == 0:
            m = chips // d
            for mb in (1, 2, 4, 8):
                for fsdp in (False, True):
                    hw = TpuHwConfig(data=d, model=m, pod=pod,
                                     microbatches=mb, fsdp=fsdp)
                    if cell.global_batch % max(hw.dp, 1) and cell.global_batch > 1:
                        continue
                    mem = memory_model(cfg, cell, hw)
                    perf = step_model(cfg, cell, hw)
                    out.append({"hw": hw, "mem": mem,
                                "feasible": mem <= hbm_limit, **perf})
        d *= 2
    out.sort(key=lambda r: (not r["feasible"], r["t_step"]))
    return out
