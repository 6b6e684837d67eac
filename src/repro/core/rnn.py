"""Cascaded recurrent (LSTM/GRU) stacks with MCD mask pre-sampling.

Structure mirrors the paper's pipelined cascade (Fig. 5): layer i's output at
time t feeds layer i+1 at time t — on the FPGA that is wave pipelining; under
XLA it is a fused scan body where all layers advance one step per iteration
(the scan carries every layer's (h, c)).  This "wavefront" scan is
mathematically identical to running layers sequentially but exposes the same
cross-layer parallelism the paper's II-balancing exploits, and it keeps the
HLO small (one scan) for pod-scale compilation.

Mask pre-sampling (paper Fig. 4 "overlap"): all masks for a forward pass are
produced *before* the scan from the counter RNG — since they are tied across
T they carry no time dimension, and since the RNG is stateless the
"pre-sampling" costs a few VPU ops, not on-chip FIFO memory.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import cells, mcd


#: Recurrent cell types ``run_stack`` (and everything above it) dispatches
#: on.  Paper §III-A: the per-gate MCD design "drops in directly" for GRU —
#: same mask-stream contract, 3 gates instead of 4, h-only carry.
CELLS = ("lstm", "gru")


def _check_cell(cell: str) -> None:
    if cell not in CELLS:
        raise ValueError(f"cell must be one of {CELLS}, got {cell!r}")


def init_stack(key: jax.Array, in_dim: int, hiddens: Sequence[int],
               dtype=jnp.float32, *, cell: str = "lstm") -> list:
    _check_cell(cell)
    init = cells.init_gru if cell == "gru" else cells.init_lstm
    params = []
    dims = [in_dim, *hiddens]
    for i, (d_in, d_h) in enumerate(zip(dims[:-1], dims[1:])):
        key, sub = jax.random.split(key)
        params.append(init(sub, d_in, d_h, dtype))
    return params


def sample_stack_masks(cfg: mcd.MCDConfig, rows: jax.Array, in_dim: int,
                       hiddens: Sequence[int], *, layer_offset: int = 0,
                       dtype=jnp.float32, cell: str = "lstm"):
    """Pre-sample (z_x, z_h) per layer; None where the layer is pointwise."""
    _check_cell(cell)
    gate_masks = mcd.gru_gate_masks if cell == "gru" else mcd.lstm_gate_masks
    masks = []
    dims = [in_dim, *hiddens]
    for i, (d_in, d_h) in enumerate(zip(dims[:-1], dims[1:])):
        layer = layer_offset + i
        if cfg.any_bayesian and cfg.bayesian(layer) and cfg.p > 0.0:
            masks.append(gate_masks(cfg.seed, layer, rows, d_in, d_h,
                                    cfg.p, dtype=dtype))
        else:
            masks.append((None, None))
    return masks


#: Sentinel masks entry: the layer is Bayesian but its masks are recomputed
#: inside the Pallas kernel — no tensors to materialize (see stack_mask_plan).
IN_KERNEL_MASKS = object()


def stack_mask_plan(cfg: mcd.MCDConfig, n_layers: int, *,
                    layer_offset: int = 0):
    """Per-layer Bayesian on/off in the shape ``run_stack`` expects of
    ``masks``, without materializing any mask tensors.

    Use with the Pallas backends, which recompute masks in-kernel from the
    counter PRNG and only need to know *whether* each layer masks — passing
    :func:`sample_stack_masks` output also works but pays the paper's
    mask-buffer cost the fused kernels exist to avoid.
    """
    return [(IN_KERNEL_MASKS, None)
            if cfg.any_bayesian and cfg.bayesian(layer_offset + i)
            and cfg.p > 0.0 else (None, None)
            for i in range(n_layers)]


def run_stack(params: Sequence, x_seq: jax.Array,
              masks, p: float, *, return_sequence: bool = True,
              backend: str = "reference", rows: jax.Array | None = None,
              seed=0, layer_offset: int = 0, interpret: bool | None = None,
              initial_state=None, lengths: jax.Array | None = None,
              return_all_states: bool = False, cell: str = "lstm",
              mesh=None, policy=None, precision: str | None = None):
    """Run a cascaded recurrent stack over a [B, T, I] sequence.

    ``cell`` selects the recurrent unit (:data:`CELLS`): ``"lstm"`` (the
    paper's main datapath) or ``"gru"`` (§III-A drop-in — 3 gates, no cell
    state).  Every backend serves both cells, and the per-layer state pytree
    follows the cell: ``(h, c)`` pairs for LSTM, ``(h,)`` 1-tuples for GRU.

    Backends (``repro.kernels.ops.LSTM_BACKENDS``):
      * ``"reference"``: the jnp wavefront scan below, consuming the
        pre-sampled ``masks`` — sharding-friendly, the numerical oracle.
      * ``"pallas_step"``: per-timestep fused kernel scanned over T.
      * ``"pallas_seq"``: sequence-fused kernel, weights resident across T.
    The Pallas backends recompute masks in-kernel from the counter PRNG, so
    they ignore the pre-sampled mask *values* and instead need the stream
    coordinates: ``rows`` (as passed to :func:`sample_stack_masks`), ``seed``
    (``cfg.seed``) and ``layer_offset``.  A layer whose ``masks`` entry is
    ``(None, None)`` runs with p=0 on every backend.

    Streaming session state (all three backends, both cells):
      * ``initial_state``: per-layer list of state tuples resuming a
        previous chunk's carry (``None`` entries or ``None`` itself = zeros).
        Feed back exactly what ``return_all_states=True`` returned — the
        carry dtypes round-trip losslessly, keeping chunked == unchunked
        bit-identical per backend (Pallas backends hand back LSTM ``c`` in
        fp32, the 32-bit cell-state policy; the GRU carry is ``h`` in the
        activation dtype on every backend).
      * ``lengths``: int [B] freezing each row's state once ``t >= length``
        so ragged chunks can pad to a common T in one batched launch.
      * ``return_all_states=True``: the second return value becomes the full
        per-layer ``[(h_T, c_T), ...]`` (LSTM) / ``[(h_T,), ...]`` (GRU)
        list (what a session must store).

    Multi-device execution (``repro.launch.rnn_shardings``):
      * ``mesh``: a jax Mesh — batch rows (sessions × MC chains) partition
        over its data axes via ``shard_map`` around the Pallas kernels;
        wide-H stacks (and the reference backend) run GSPMD-partitioned
        instead.  Sharded output is **bit-identical** to the unsharded
        lengths-enabled run at any device count: masks key off global
        ``(seed, rows)`` coordinates, and the sharded path always runs the
        lengths-pinned graph family (full-T lengths are synthesized when
        ``lengths`` is None — pass ``lengths`` explicitly to compare
        against an unsharded run bit-for-bit).
      * ``policy``: a ``StackShardingPolicy`` (axis names, data/gspmd
        strategy, the wide-H threshold); None = the default policy.

    Serving precision (``repro.kernels.quantize.PRECISIONS``):
      * ``precision``: None (native dtypes — the default), ``"fp32"``,
        ``"bf16"`` (cast), ``"int8"`` / ``"int4"`` (per-output-channel
        quantized weights over bf16 activations, fp32 accumulate).  ``x_seq``
        is cast to the precision's activation dtype up front; the fp32
        master ``params`` are quantized/cast in-graph, never mutated.  The
        sequence kernels keep the int codes VMEM-resident and dequantize
        in-register; the step and reference backends apply the identical
        canonical dequant outside, so all three backends stay bit-identical
        at every precision.  The reference backend needs ``masks`` sampled
        in the activation dtype (``sample_stack_masks(..., dtype=act)``) —
        mask values carry the 1/(1-p) scale, which the kernels materialize
        in the activation dtype.

    Returns (outputs [B, T, H_last] if return_sequence else None,
             the last layer's state — ``(h_T, c_T)`` / ``(h_T,)`` — or the
             per-layer list).
    """
    _check_cell(cell)
    if precision is not None:
        # deferred: core must import without the kernels package eagerly
        from repro.kernels import quantize
        quantize.check_precision(precision)
        x_seq = x_seq.astype(quantize.activation_dtype(precision,
                                                       x_seq.dtype))
    if mesh is not None:
        # deferred: core must import without the launch layer (and jax
        # device state must stay untouched until a mesh actually exists)
        from repro.launch import rnn_shardings
        return rnn_shardings.run_stack_sharded(
            params, x_seq, masks, p, mesh=mesh, policy=policy,
            backend=backend, return_sequence=return_sequence, rows=rows,
            seed=seed, layer_offset=layer_offset, interpret=interpret,
            initial_state=initial_state, lengths=lengths,
            return_all_states=return_all_states, cell=cell,
            precision=precision)
    if backend != "reference":
        return _run_stack_pallas(params, x_seq, masks, p, backend=backend,
                                 return_sequence=return_sequence, rows=rows,
                                 seed=seed, layer_offset=layer_offset,
                                 interpret=interpret,
                                 initial_state=initial_state, lengths=lengths,
                                 return_all_states=return_all_states,
                                 cell=cell, precision=precision)
    if any(zx is IN_KERNEL_MASKS for zx, _ in masks):
        raise ValueError("stack_mask_plan() entries carry no mask values; "
                         "the reference backend needs sample_stack_masks()")
    if precision is not None:
        # Fake-quantize in core [G, I/H, H] layout (contraction axis 1) —
        # bit-identical (q, scale) to the kernels' [I/H, G, H] axis-0
        # quantization: the reductions cover the same element sets and every
        # other op is elementwise.
        params = [lp._replace(
            wx=quantize.fake_quant(lp.wx, precision, axis=1,
                                   act_dtype=x_seq.dtype),
            wh=quantize.fake_quant(lp.wh, precision, axis=1,
                                   act_dtype=x_seq.dtype))
            for lp in params]
    batch = x_seq.shape[0]
    dtype = x_seq.dtype
    # Under a serving precision the reference matches the kernels' 32-bit
    # cell-state policy: c seeds/carries/returns fp32 even for bf16 h.
    c_dtype = jnp.float32 if precision is not None else dtype
    carries = _seed_carries(params, initial_state, batch, dtype, cell,
                            c_dtype=c_dtype)
    xs = jnp.swapaxes(x_seq, 0, 1)  # [T, B, I] time-major for scan
    varlen = lengths is not None
    lens = lengths.astype(jnp.int32) if varlen else None
    gru = cell == "gru"
    # Student rows (mcd.STUDENT_ROW_FLAG) run deterministic on every backend;
    # the kernels read the flag off the int32 sign bit, the reference threads
    # an explicit per-row boolean into the cell steps.
    det = mcd.det_row_mask(rows) if rows is not None else None

    def step(carry, xt):
        x_t, t = xt
        new_carry = []
        inp = x_t
        for state, layer_params, (zx, zh) in zip(carry, params, masks):
            if gru:
                (h,) = state
                h_new = cells.gru_step(layer_params, h, inp, zx, zh, p,
                                       det=det)
                if varlen:
                    h_new = cells.freeze_rows_h(t, lens, h_new, h)
                new_state = (h_new,)
            else:
                h, c = state
                h_new, c_new = cells.lstm_step(layer_params, h, c, inp,
                                               zx, zh, p, det=det)
                if varlen:
                    h_new, c_new = cells.freeze_rows(t, lens, h_new, c_new,
                                                     h, c)
                new_state = (h_new, c_new)
            new_carry.append(new_state)
            inp = h_new
        return new_carry, (inp if return_sequence else jnp.zeros((0,), dtype))

    ts = jnp.arange(x_seq.shape[1], dtype=jnp.int32)
    final_carry, ys = jax.lax.scan(step, carries, (xs, ts))
    out = jnp.swapaxes(ys, 0, 1) if return_sequence else None
    return out, (final_carry if return_all_states else final_carry[-1])


def _seed_carries(params, initial_state, batch, dtype, cell="lstm",
                  c_dtype=None):
    """Per-layer state carries: zeros, or the resumed session state as-is.

    Cell-aware pytrees: LSTM layers carry ``(h, c)``, GRU layers ``(h,)``.
    ``c_dtype`` (default: ``dtype``) seeds the LSTM cell state — fp32 under
    a serving precision, matching the kernels' 32-bit cell-state policy.
    """
    parts = 1 if cell == "gru" else 2
    dtypes = (dtype, c_dtype or dtype)[:parts]
    carries = []
    for i, layer_params in enumerate(params):
        hidden = layer_params.wh.shape[-1]
        state = initial_state[i] if initial_state is not None else None
        if state is None:
            state = tuple(jnp.zeros((batch, hidden), dt) for dt in dtypes)
        carries.append(tuple(state))
    return carries


def _run_stack_pallas(params, x_seq, masks, p, *, backend, return_sequence,
                      rows, seed, layer_offset, interpret, initial_state,
                      lengths, return_all_states, cell, precision=None):
    """Kernel-backed stack: layers run whole-sequence, one after another.

    The wavefront trick above exists to fuse the scan body across layers; the
    kernels already fuse a full layer (step- or sequence-level), so here the
    cascade is the plain layer-by-layer composition — identical math.
    """
    from repro.kernels import ops  # deferred: core must import without pallas

    if backend not in ops.LSTM_BACKENDS:
        raise ValueError(f"backend must be one of {ops.LSTM_BACKENDS}, "
                         f"got {backend!r}")
    if rows is None:
        raise ValueError(f"backend={backend!r} needs the mask-stream `rows` "
                         "(the same ids passed to sample_stack_masks)")
    seq = backend == "pallas_seq"
    gru = cell == "gru"
    stack_layer = ops.gru_stack_layer if gru else ops.lstm_stack_layer
    inp = x_seq
    states = []
    for i, (layer_params, (zx, _)) in enumerate(zip(params, masks)):
        p_eff = p if zx is not None else 0.0
        state0 = initial_state[i] if initial_state is not None else None
        # The host side of each layer's dispatch, as a profiler span.
        with jax.profiler.TraceAnnotation(f"rnn.layer{i}"):
            inp, carry = stack_layer(*layer_params, inp, rows, seed,
                                     layer_offset + i, p_eff, seq=seq,
                                     initial_state=state0,
                                     lengths=lengths, precision=precision,
                                     interpret=interpret)
        states.append(carry)
    out = inp if return_sequence else None
    if return_all_states:
        # Session-resume form: LSTM c stays fp32 (the kernels' carry dtype),
        # so a chunk boundary round-trips the cell state losslessly; the GRU
        # carry is h in the activation dtype already.
        return out, states
    if gru:
        return out, states[-1]                  # (h_T,) — no dtype to match
    # Match the reference carry contract: c in the input dtype (the kernels
    # hand back their fp32 accumulator).  Under a serving precision the
    # reference itself carries c in fp32, so no cast.
    hT, cT = states[-1]
    return out, (hT, cT if precision is not None else cT.astype(x_seq.dtype))
