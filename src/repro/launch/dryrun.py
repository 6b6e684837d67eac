import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
mesh; record roofline terms.  The two lines above MUST stay first — jax locks
the device count on first init (do not set this flag globally).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod --out results/
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ALIASES, get_config
from repro.launch import analysis, mesh as mesh_lib, specs
from repro.models.config import SHAPES, shape_applicable


def run_cell(arch: str, shape: str, multi_pod: bool, *,
             microbatches: int = 1, verbose: bool = True,
             probes: bool = True, opts: tuple = ()) -> dict:
    import contextlib

    from repro.launch import mesh as _m
    from repro.models import backbone as _bb
    from repro.models import moe as _moe

    cfg = get_config(arch)
    record = {"arch": arch, "shape": shape,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "kind": SHAPES[shape].kind, "opts": list(opts)}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = why
        return record
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    dp = _m.dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= _m.axis_sizes(mesh)[a]
    opt_stack = contextlib.ExitStack()
    if "moe_group" in opts:
        opt_stack.enter_context(_moe.moe_sharding(
            expert_axis="model", token_axes=dp, groups=dp_size))
    if "seqpar" in opts:
        opt_stack.enter_context(_bb.activation_sharding(
            spec=(dp, "model", None)))
    t0 = time.time()
    try:
        job = specs.make_job(cfg, shape, mesh)
        if SHAPES[shape].kind == "train" and microbatches > 1:
            job = specs.train_job(cfg, shape, mesh, microbatches=microbatches)
        if SHAPES[shape].kind == "decode" and "kv8" in opts:
            job = specs.decode_job(cfg, shape, mesh, kv_quant=True)
        with opt_stack, jax.set_mesh(mesh):
            lowered = jax.jit(job.fn, in_shardings=job.in_shardings,
                              out_shardings=job.out_shardings).lower(*job.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            print(compiled.memory_analysis())
            hlo = compiled.as_text()
            roof = analysis.analyse(compiled, analysis.MODELED_DEVICE, hlo)
            ca = compiled.cost_analysis() or {}
            print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
        mf = analysis.model_flops(cfg, SHAPES[shape], chips)
        record.update({
            "status": "ok",
            "chips": chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "raw_flops_per_device": roof.flops,
            "raw_bytes_per_device": roof.bytes_hbm,
            "raw_collective_bytes_per_device": roof.bytes_collective,
            "model_flops_per_device": mf,
            "memory": roof.memory_per_device,
        })
        if probes:
            record.update(run_probes(cfg, shape, mesh, opts=opts))
            record["useful_flops_ratio"] = (
                mf / record["flops_per_device"]
                if record.get("flops_per_device") else 0.0)
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    if verbose:
        s = record["status"]
        extra = (f" bottleneck={record.get('bottleneck')}"
                 f" t=({record.get('t_compute', 0):.3e},"
                 f"{record.get('t_memory', 0):.3e},"
                 f"{record.get('t_collective', 0):.3e})s"
                 if s == "ok" else record.get("reason", record.get("error", "")))
        print(f"[dryrun] {arch} × {shape} × {record['mesh']}: {s}{extra}",
              flush=True)
    return record


def run_probes(cfg, shape: str, mesh, opts: tuple = ()) -> dict:
    """Compile per-block probes and compose the corrected roofline
    (Σ body × repeat + head + opt — see specs.probe_jobs docstring)."""
    import contextlib

    from repro.launch import mesh as _m
    from repro.models import backbone as _bb
    from repro.models import layers as L
    from repro.models import moe as _moe

    cell = SHAPES[shape]
    tot = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
    details = []
    dp = _m.dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= _m.axis_sizes(mesh)[a]
    stack = contextlib.ExitStack()
    if "moe_group" in opts:
        stack.enter_context(_moe.moe_sharding(
            expert_axis="model", token_axes=dp, groups=dp_size))
    if "seqpar" in opts:
        stack.enter_context(_bb.activation_sharding(spec=(dp, "model", None)))
    with stack, L.attention_override(**specs._attn_blocks_for(cell.seq_len)):
        for pr in specs.probe_jobs(cfg, shape, mesh,
                                   kv_quant="kv8" in opts):
            with jax.set_mesh(mesh):
                compiled = jax.jit(
                    pr.fn, in_shardings=pr.in_shardings).lower(
                        *pr.args).compile()
                roof = analysis.analyse(compiled, analysis.MODELED_DEVICE)
            tot["flops"] += roof.flops * pr.multiplier
            tot["bytes"] += roof.bytes_hbm * pr.multiplier
            tot["coll"] += roof.bytes_collective * pr.multiplier
            details.append({
                "probe": pr.name, "multiplier": pr.multiplier,
                "flops": roof.flops, "bytes": roof.bytes_hbm,
                "collective_bytes": roof.bytes_collective,
                "collectives": roof.coll_by_kind})
    pk = analysis.peaks(analysis.MODELED_DEVICE)
    t_c = tot["flops"] / pk.flops
    t_m = tot["bytes"] / pk.hbm_bw
    t_x = tot["coll"] / pk.ici_bw
    bott = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    return {
        "flops_per_device": tot["flops"],
        "bytes_per_device": tot["bytes"],
        "collective_bytes_per_device": tot["coll"],
        "t_compute": t_c, "t_memory": t_m, "t_collective": t_x,
        "bottleneck": bott,
        "roofline_fraction": t_c / max(t_c, t_m, t_x, 1e-30),
        "probes": details,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALIASES))
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-probes", action="store_true",
                    help="compile-only (skip roofline probe composition)")
    ap.add_argument("--opt", action="append", default=[],
                    choices=("moe_group", "seqpar", "kv8"),
                    help="optimization variants (§Perf)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    cells = []
    archs = sorted(ALIASES) if args.all or not args.arch else [args.arch]
    shapes = sorted(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    records = []
    for arch, shape, mp in cells:
        rec = run_cell(arch, shape, mp, microbatches=args.microbatches,
                       probes=not args.no_probes, opts=tuple(args.opt))
        records.append(rec)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = len(records) - n_ok - n_skip
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
