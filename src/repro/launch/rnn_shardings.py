"""Sharded execution of the recurrent stack: the multi-device data plane.

The paper's FPGA scales by spending parallelism knobs (reuse factors
R_x/R_h); the TPU analogue is *device* parallelism, and this module is
where the recurrent stack learns it.  ``rnn.run_stack(..., mesh=...)``
lands here and picks one of two strategies over a ``(data, model)`` mesh
(`repro.launch.mesh` builds the production shapes):

* ``"data"`` — the serving hot path.  Batch rows (sessions × MC chains)
  partition over the ``data`` axes via ``shard_map``; every device runs
  the *unmodified* sequence-fused Pallas kernel on its batch shard with
  the weights replicated.  This is Fan et al.'s trick of replicating
  Monte-Carlo samples across compute units, applied at mesh scale: MC
  chains are batch rows here, so sharding the batch *is* sharding the
  chains.
* ``"gspmd"`` — the wide-H fallback.  docs/kernels.md explains why a
  hidden-tile grid axis cannot live inside the sequence kernel (step t
  needs all H columns of h_{t-1}); when H outgrows one core's VMEM the
  stack instead runs the ``"reference"`` jnp scan under GSPMD with the
  weights' H *output* dim sharded over the ``model`` axis (contractions
  stay unsplit — XLA all-gathers the small per-step ``h``, never splits a
  reduction) and the batch over ``data``.

Determinism contract (what makes sharded == unsharded **bit-identical**
at any device count, pinned by ``tests/test_rnn_sharding.py``):

1. Masks are pure functions of global ``(seed, rows)`` coordinates
   (docs/architecture.md).  ``rows`` ride the batch axis into each shard,
   so a shard draws exactly the bits the unsharded run draws for those
   rows — there is no per-device RNG anywhere.
2. The sharded path always runs the **lengths-pinned graph family**:
   when the caller passes no ``lengths`` it synthesizes full-T lengths.
   That family is bit-identical across launch sizes, splits and backends
   (the freeze-select pins XLA fusion — docs/kernels.md), so slicing the
   batch across devices cannot change any row's numerics.
3. Batch padding (to a device-count multiple) only ever appends rows,
   whose outputs are sliced off; per-row math never sees its neighbours.

Policy knobs live in :class:`StackShardingPolicy`; ``"auto"`` picks
``"data"`` for the Pallas backends until H exceeds the per-core VMEM
budget, then falls back to ``"gspmd"`` (and always uses ``"gspmd"`` for
the reference backend, which is GSPMD-native).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import mcd, rnn
from repro.launch import mesh as mesh_lib

#: H above which ``"auto"`` stops replicating the sequence kernel's weights.
#: docs/kernels.md: resident weights ≈ 2·G·H·(I+H) bytes in bf16 against a
#: ~16 MB VMEM core — a few hundred to ~1k columns; beyond that the kernel's
#: whole-H-per-program design is the wrong tool and GSPMD H-tiling takes over.
WIDE_H_DEFAULT = 1024

STRATEGIES = ("auto", "data", "gspmd")


@dataclasses.dataclass(frozen=True)
class StackShardingPolicy:
    """How the recurrent stack maps onto a mesh (the sharding half of DSE).

    Attributes:
      data: mesh axes carrying batch rows (``("pod", "data")`` on multi-pod
        meshes — only axes actually present on the mesh are used).
      model: mesh axis carrying the hidden width in the GSPMD fallback.
      strategy: ``"data"`` (shard_map batch partition over the Pallas
        kernels), ``"gspmd"`` (reference scan, H over ``model``), or
        ``"auto"`` (data until ``wide_h``, gspmd beyond — and always gspmd
        for the reference backend).
      wide_h: the VMEM-residency threshold ``"auto"`` switches at.
    """

    data: tuple[str, ...] = ("pod", "data")
    model: str = "model"
    strategy: str = "auto"
    wide_h: int = WIDE_H_DEFAULT

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(f"strategy must be one of {STRATEGIES}, "
                             f"got {self.strategy!r}")


DEFAULT_POLICY = StackShardingPolicy()


def data_axes(mesh, policy: StackShardingPolicy = DEFAULT_POLICY):
    """The policy's data axes actually present on this mesh, mesh-ordered.

    Returns ``None`` (the replicated PartitionSpec entry) when the mesh has
    no data axis at all, so the specs below degrade gracefully.
    """
    axes = tuple(a for a in mesh.axis_names if a in policy.data)
    return axes or None


def data_size(mesh, policy: StackShardingPolicy = DEFAULT_POLICY) -> int:
    sizes = mesh_lib.axis_sizes(mesh)
    out = 1
    for a in (data_axes(mesh, policy) or ()):
        out *= sizes[a]
    return out


def model_size(mesh, policy: StackShardingPolicy = DEFAULT_POLICY) -> int:
    return mesh_lib.axis_sizes(mesh).get(policy.model, 1)


def resolve_strategy(mesh, policy: StackShardingPolicy, backend: str,
                     hiddens) -> str:
    """Pick the execution strategy for this (mesh, backend, stack) triple."""
    if policy.strategy != "auto":
        return policy.strategy
    if backend == "reference":
        return "gspmd"              # the jnp scan is GSPMD-native
    if max(hiddens) > policy.wide_h and model_size(mesh, policy) > 1:
        return "gspmd"              # H-tiling cannot live inside the kernel
    return "data"


# ---------------------------------------------------------------------------
# PartitionSpecs mirroring the stack structures (the rnn analogue of
# launch/shardings.py's structural spec builders)
# ---------------------------------------------------------------------------

def _param_specs(cell: str, hiddens, mesh,
                 policy: StackShardingPolicy, strategy: str):
    """The one place the H-sharding rule lives (both entry points below
    and the jitted gspmd factory call this)."""
    from repro.core import cells
    cls = cells.GRUParams if cell == "gru" else cells.LSTMParams
    tp = policy.model if policy.model in mesh.axis_names else None
    ms = model_size(mesh, policy)

    def out_dim(h):
        if strategy != "gspmd" or tp is None or h % max(ms, 1) or ms <= 1:
            return None
        return tp

    return [cls(wx=P(None, None, out_dim(h)),
                wh=P(None, None, out_dim(h)),
                b=P(None, out_dim(h))) for h in hiddens]


def stack_param_specs(params, mesh, policy: StackShardingPolicy = DEFAULT_POLICY,
                      *, strategy: str = "data"):
    """Per-layer PartitionSpecs for core-layout stack weights.

    Core layout (``cells.LSTMParams``/``GRUParams``): ``wx [G, I, H]``,
    ``wh [G, H, H]``, ``b [G, H]``.  The ``"data"`` strategy replicates
    weights (each shard runs the full kernel); ``"gspmd"`` shards the H
    *output* dim over ``model`` where divisible — never a contraction dim,
    so no reduction is ever split (the bit-identity argument above).
    """
    from repro.core import cells
    cell = "gru" if isinstance(params[0], cells.GRUParams) else "lstm"
    return _param_specs(cell, tuple(lp.wh.shape[-1] for lp in params),
                        mesh, policy, strategy)


def carry_specs(n_layers: int, mesh,
                policy: StackShardingPolicy = DEFAULT_POLICY,
                *, cell: str = "lstm"):
    """Per-layer state specs: ``[B, H]`` parts shard batch over data axes.

    The pytree arity follows the cell — ``(h, c)`` for LSTM, ``(h,)`` for
    GRU — exactly what ``run_stack(return_all_states=True)`` hands back
    (and what the execution factories below use for carries in and out).
    """
    dp = data_axes(mesh, policy)
    parts = 1 if cell == "gru" else 2
    return [tuple(P(dp, None) for _ in range(parts))
            for _ in range(n_layers)]


def batch_specs(mesh, policy: StackShardingPolicy = DEFAULT_POLICY) -> dict:
    """Specs for the batch-aligned operands: x_seq, mask rows, lengths.

    ``rows`` shard with the batch: each device receives the *global* mask
    coordinates of its rows, which is the whole determinism story — masks
    are functions of coordinates, not of device ids.
    """
    dp = data_axes(mesh, policy)
    return {"x_seq": P(dp, None, None), "rows": P(dp), "lengths": P(dp)}


# ---------------------------------------------------------------------------
# Entry point (run_stack's mesh= dispatch lands here)
# ---------------------------------------------------------------------------

def run_stack_sharded(params, x_seq, masks, p, *, mesh,
                      policy: StackShardingPolicy | None = None,
                      backend: str = "pallas_seq", return_sequence: bool = True,
                      rows=None, seed=0, layer_offset: int = 0,
                      interpret: bool | None = None, initial_state=None,
                      lengths=None, return_all_states: bool = False,
                      cell: str = "lstm", precision: str | None = None):
    """Run the stack sharded over ``mesh`` — same contract as ``run_stack``.

    Callers use ``rnn.run_stack(..., mesh=..., policy=...)``; this is the
    implementation.  The sharded path always runs the lengths-pinned graph
    family (synthesizing full-T lengths when the caller passes none), so
    its output is bit-identical to the unsharded lengths-enabled run at
    any device count — including 1, which makes ``mesh=`` safe to leave on
    everywhere.  ``precision`` follows ``run_stack``'s serving-precision
    contract: the input is cast to the activation dtype *before* staging,
    so the gspmd strategy's in-graph mask draws sample in the same dtype
    the kernels materialize the 1/(1-p) scale in, and sharded stays
    bit-identical to unsharded per precision.
    """
    policy = policy or DEFAULT_POLICY
    if rows is None:
        raise ValueError("mesh= needs the mask-stream `rows` (the global "
                         "coordinates are what keep sharded masks "
                         "deterministic per logical row)")
    if precision is not None:
        from repro.kernels import quantize
        quantize.check_precision(precision)
        x_seq = x_seq.astype(quantize.activation_dtype(precision,
                                                       x_seq.dtype))
    hiddens = [lp.wh.shape[-1] for lp in params]
    strategy = resolve_strategy(mesh, policy, backend, hiddens)
    if lengths is None:
        # Pin the graph family: the freeze-select is what makes the batch
        # split across devices numerically invisible (docs/kernels.md).
        lengths = jnp.full((x_seq.shape[0],), x_seq.shape[1], jnp.int32)
    kw = dict(p=p, return_sequence=return_sequence, rows=rows, seed=seed,
              layer_offset=layer_offset, interpret=interpret,
              initial_state=initial_state, lengths=lengths,
              return_all_states=return_all_states, cell=cell,
              precision=precision)
    if strategy == "gspmd":
        return _run_gspmd(params, x_seq, masks, mesh=mesh, policy=policy,
                          **kw)
    return _run_data_sharded(params, x_seq, masks, mesh=mesh, policy=policy,
                             backend=backend, **kw)


def _pad_batch(arr, pad, value=0):
    if pad == 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, widths, constant_values=value)


def _shard_pad(batch: int, ndev: int) -> int:
    """Rows to append so the batch shards evenly with ≥ 2 rows per device.

    The two-row floor is numeric, not cosmetic: a single-row shard would
    launch the kernel's ``[1, I] @ [I, G·H]`` matvec codepath, whose
    reduction rounds differently from the batched matmul every other
    launch shape takes — the one shape the "bit-identical across launch
    sizes" pin does not cover.  ``ndev == 1`` never pads: the single shard
    then runs the *exact* unsharded launch.
    """
    if ndev <= 1:
        return 0
    per_shard = max(2, -(-batch // ndev))
    return per_shard * ndev - batch


def _split_masks(masks):
    """Separate shardable mask arrays from the static plan (sentinels/None).

    Returns (static_plan, value_tree): the plan keeps ``IN_KERNEL_MASKS`` /
    ``None`` markers (hashable — they key the compiled-callable cache), the
    value tree carries only real arrays (shard_map / jit operands).  Host
    numpy masks count as arrays too — an ndarray in the *plan* would be an
    unhashable cache key (and wrongly baked into the compiled graph).
    """
    is_arr = lambda v: isinstance(v, (jax.Array, np.ndarray))
    plan, values = [], []
    for zx, zh in masks:
        plan.append((None if is_arr(zx) else zx,
                     None if is_arr(zh) else zh))
        values.append((jnp.asarray(zx) if is_arr(zx) else None,
                       jnp.asarray(zh) if is_arr(zh) else None))
    return tuple(plan), values


def _merge_masks(plan, values):
    return [(vx if vx is not None else px, vh if vh is not None else ph)
            for (px, ph), (vx, vh) in zip(plan, values)]


def _stage_batch(x_seq, rows, lengths, initial_state, mask_vals, ndev):
    """Pad every batch-aligned operand for an even ≥2-rows/shard split.

    Shared by both strategies — the padding contract (appended rows get
    mask-row 0 and length 1, outputs sliced off by :func:`_unpad`) must
    never diverge between them.  Returns
    ``(B, pad, x, rows, lengths, state, mask_vals, presence)``.
    """
    B = x_seq.shape[0]
    pad = _shard_pad(B, ndev)
    x_p = _pad_batch(x_seq, pad)
    rows_p = _pad_batch(jnp.asarray(rows, jnp.uint32), pad)
    lens_p = _pad_batch(jnp.asarray(lengths, jnp.int32), pad, value=1)
    state_p = None
    if initial_state is not None:
        state_p = [tuple(_pad_batch(part, pad) for part in layer)
                   for layer in initial_state]
    mask_p = [tuple(None if v is None else _pad_batch(v, pad)
                    for v in pair) for pair in mask_vals]
    presence = tuple((vx is not None, vh is not None)
                     for vx, vh in mask_vals)
    return B, pad, x_p, rows_p, lens_p, state_p, mask_p, presence


def _unpad(out, states, B, pad):
    if not pad:
        return out, states
    return (None if out is None else out[:B],
            [tuple(part[:B] for part in layer) for layer in states])


def _finalize(out, states, x_dtype, *, backend, cell, return_all_states,
              precision=None):
    """Match run_stack's non-all-states return contract after an
    always-all-states inner run."""
    if return_all_states:
        return out, states
    last = states[-1]
    if cell == "gru" or backend == "reference" or precision is not None:
        # Under a serving precision every backend keeps c fp32 (run_stack's
        # 32-bit cell-state policy) — no cast to the activation dtype.
        return out, last
    h_t, c_t = last
    return out, (h_t, c_t.astype(x_dtype))


@functools.lru_cache(maxsize=512)
def _data_sharded_fn(mesh, dp, backend, cell, p, layer_offset, interpret,
                     return_sequence, plan, presence, has_state, n_layers,
                     precision=None):
    """Build (once per static signature) the jitted shard_map callable.

    The cache is what makes the sharded path servable: a fresh closure per
    tick would re-trace and re-lower every call.  Everything in the key is
    hashable and everything per-tick (arrays, seed) is an operand, so a
    streaming engine's ticks hit one compiled executable per launch shape
    — the same economics as the unsharded jit path.
    """
    def local(params_, x_, mvals_, rows_, seed_, lens_, state_):
        out, states = rnn.run_stack(
            params_, x_, _merge_masks(plan, mvals_), p,
            return_sequence=return_sequence, backend=backend, rows=rows_,
            seed=seed_, layer_offset=layer_offset, interpret=interpret,
            initial_state=state_, lengths=lens_, return_all_states=True,
            cell=cell, precision=precision)
        return out, states

    po = StackShardingPolicy(data=dp or ())
    bs = batch_specs(mesh, po)
    mspec = tuple((bs["x_seq"] if px else None, bs["x_seq"] if ph else None)
                  for px, ph in presence)        # masks are [B, G, dim] too
    cspec = carry_specs(n_layers, mesh, po, cell=cell)
    out_spec = (bs["x_seq"] if return_sequence else None, cspec)
    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), bs["x_seq"], mspec, bs["rows"], P(), bs["lengths"],
                  cspec if has_state else None),
        out_specs=out_spec, check_vma=False)
    return jax.jit(sharded)


def _run_data_sharded(params, x_seq, masks, *, mesh, policy, backend, p,
                      return_sequence, rows, seed, layer_offset, interpret,
                      initial_state, lengths, return_all_states, cell,
                      precision=None):
    """Batch rows over the data axes via shard_map; weights replicated.

    Every device runs the unmodified Pallas (or reference) stack on its
    batch shard.  The batch pads up to a device-count multiple (appended
    rows are discarded), so any session count shards.
    """
    ndev = data_size(mesh, policy)
    dp = data_axes(mesh, policy)
    plan, mask_vals = _split_masks(masks)
    B, pad, x_p, rows_p, lens_p, state_p, mask_p, presence = _stage_batch(
        x_seq, rows, lengths, initial_state, mask_vals, ndev)

    fn = _data_sharded_fn(mesh, dp, backend, cell, float(p),
                          int(layer_offset), interpret, bool(return_sequence),
                          plan, presence, state_p is not None, len(params),
                          precision)
    out, states = fn(params, x_p, tuple(mask_p), rows_p,
                     jnp.asarray(seed, jnp.uint32), lens_p, state_p)
    out, states = _unpad(out, states, B, pad)
    return _finalize(out, states, x_seq.dtype, backend=backend, cell=cell,
                     return_all_states=return_all_states, precision=precision)


@functools.lru_cache(maxsize=512)
def _gspmd_fn(mesh, policy, cell, p, layer_offset, return_sequence, plan,
              presence, has_state, in_dims, hiddens, precision=None):
    """Build (once per static signature) the GSPMD-jitted reference scan.

    Same caching rationale as :func:`_data_sharded_fn`; param specs come
    from the same :func:`_param_specs` rule the public spec builder uses.
    A ``plan`` entry that is still the ``IN_KERNEL_MASKS`` sentinel (a
    Pallas-backed caller's ``stack_mask_plan``) has its mask values drawn
    *inside* the jitted fn from the same ``(seed, layer, rows)``
    coordinates the kernels use — same bits (the mask-stream contract),
    but fused into the compiled graph instead of re-dispatched eagerly
    every call.
    """
    ns = functools.partial(NamedSharding, mesh)
    gate_masks = mcd.gru_gate_masks if cell == "gru" else mcd.lstm_gate_masks
    pspec = _param_specs(cell, hiddens, mesh, policy, "gspmd")
    bs = batch_specs(mesh, policy)
    mspec = [(bs["x_seq"] if px else None, bs["x_seq"] if ph else None)
             for px, ph in presence]             # masks are [B, G, dim] too
    cspec = carry_specs(len(hiddens), mesh, policy, cell=cell)
    out_spec = (bs["x_seq"] if return_sequence else None, cspec)

    def fn(params_, x_, mvals_, rows_, seed_, lens_, state_):
        masks_ = []
        for i, (zx, zh) in enumerate(_merge_masks(plan, mvals_)):
            if zx is rnn.IN_KERNEL_MASKS:
                masks_.append(gate_masks(seed_, layer_offset + i, rows_,
                                         in_dims[i], hiddens[i], p,
                                         dtype=x_.dtype))
            else:
                masks_.append((zx, zh))
        return rnn.run_stack(params_, x_, masks_, p,
                             return_sequence=return_sequence,
                             backend="reference", rows=rows_,
                             initial_state=state_, lengths=lens_,
                             return_all_states=True, cell=cell,
                             precision=precision)

    to_ns = lambda tree: jax.tree.map(ns, tree,
                                      is_leaf=lambda s: isinstance(s, P))
    return jax.jit(fn,
                   in_shardings=to_ns((pspec, bs["x_seq"], mspec,
                                       bs["rows"], P(), bs["lengths"],
                                       cspec if has_state else None)),
                   out_shardings=to_ns(out_spec))


def _run_gspmd(params, x_seq, masks, *, mesh, policy, p, return_sequence,
               rows, seed, layer_offset, interpret, initial_state, lengths,
               return_all_states, cell, precision=None):
    """Wide-H strategy: reference scan under GSPMD, H over ``model``.

    Weights shard on their H *output* dim only (never a contraction dim —
    per-element results stay bit-identical; XLA all-gathers the small
    per-step ``h`` instead of splitting a reduction), batch rows and mask
    coordinates over the data axes.  This is the H-tiling docs/kernels.md
    says cannot live inside the sequence kernel.
    """
    del interpret  # reference scan — nothing to interpret
    plan, mask_vals = _split_masks(masks)
    # GSPMD's explicit in_shardings need the batch divisible just like
    # shard_map does — same staging, same padding contract.
    B, pad, x_p, rows_p, lens_p, state_p, mask_p, presence = _stage_batch(
        x_seq, rows, lengths, initial_state, mask_vals,
        data_size(mesh, policy))

    jf = _gspmd_fn(mesh, policy, cell, float(p), int(layer_offset),
                   bool(return_sequence), plan, presence,
                   state_p is not None,
                   tuple(lp.wx.shape[1] for lp in params),
                   tuple(lp.wh.shape[-1] for lp in params), precision)
    out, states = jf(params, x_p, mask_p, rows_p,
                     jnp.asarray(seed, jnp.uint32), lens_p, state_p)
    out, states = _unpad(out, states, B, pad)
    return _finalize(out, states, x_seq.dtype, backend="reference", cell=cell,
                     return_all_states=return_all_states, precision=precision)
