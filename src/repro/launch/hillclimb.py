import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb driver: compile ONE probe (or the full composition) under
named optimization variants and print the roofline deltas — the fast
hypothesis → change → measure loop.

  PYTHONPATH=src python -m repro.launch.hillclimb --arch olmoe-1b-7b \
      --shape train_4k --variants base,moe_shard
"""

import argparse
import contextlib
import json

import jax

from repro.configs import ALIASES, get_config
from repro.launch import analysis, mesh as mesh_lib, specs
from repro.models import backbone, layers, moe
from repro.models.config import SHAPES


@contextlib.contextmanager
def variant_ctx(names: set[str], mesh):
    """Compose optimization contexts by name."""
    dp = mesh_lib.dp_axes(mesh)
    with contextlib.ExitStack() as stack:
        if "moe_shard" in names:
            stack.enter_context(moe.moe_sharding(expert_axis="model",
                                                 token_axes=dp))
        if "moe_group" in names:
            dp_size = 1
            for a in dp:
                dp_size *= mesh_lib.axis_sizes(mesh)[a]
            stack.enter_context(moe.moe_sharding(
                expert_axis="model", token_axes=dp, groups=dp_size))
        if "seqpar" in names:
            stack.enter_context(backbone.activation_sharding(
                spec=(dp, "model", None)))
        if "flash_block" in names:
            stack.enter_context(layers.attention_override(
                q_block=256, kv_block=512))
        yield


def measure(arch: str, shape: str, variants: set[str], *,
            probe_filter: str | None = None, multi_pod: bool = False):
    import dataclasses
    cfg = get_config(arch)
    for v in variants:
        if v.startswith("chunk") and cfg.ssm is not None:
            cfg = cfg.replace(ssm=dataclasses.replace(cfg.ssm,
                                                      chunk=int(v[5:])))
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    cell = SHAPES[shape]
    tot = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
    details = []
    attn_probe_cfg = specs._attn_blocks_for(cell.seq_len)
    if "flash_block" in variants:
        attn_probe_cfg = dict(q_block=max(256, cell.seq_len // 16),
                              kv_block=max(512, cell.seq_len // 16),
                              unroll=True)
    with layers.attention_override(**attn_probe_cfg):
        with variant_ctx(variants - {"flash_block"}, mesh):
            for pr in specs.probe_jobs(cfg, shape, mesh,
                                       kv_quant="kv8" in variants):
                if probe_filter and probe_filter not in pr.name:
                    continue
                with jax.set_mesh(mesh):
                    compiled = jax.jit(
                        pr.fn, in_shardings=pr.in_shardings).lower(
                            *pr.args).compile()
                    roof = analysis.analyse(compiled, analysis.MODELED_DEVICE)
                tot["flops"] += roof.flops * pr.multiplier
                tot["bytes"] += roof.bytes_hbm * pr.multiplier
                tot["coll"] += roof.bytes_collective * pr.multiplier
                details.append((pr.name, pr.multiplier, roof))
    pk = analysis.peaks(analysis.MODELED_DEVICE)
    t_c = tot["flops"] / pk.flops
    t_m = tot["bytes"] / pk.hbm_bw
    t_x = tot["coll"] / pk.ici_bw
    return {"t_compute": t_c, "t_memory": t_m, "t_collective": t_x,
            "t_bound": max(t_c, t_m, t_x),
            "bottleneck": max((t_c, "compute"), (t_m, "memory"),
                              (t_x, "collective"))[1],
            "details": details, **tot}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ALIASES))
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--variants", default="base",
                    help="comma list of variant sets separated by ';' "
                         "e.g. 'base;moe_shard;moe_shard+seqpar'")
    ap.add_argument("--probe", default=None, help="probe-name filter")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    records = []
    for vs in args.variants.split(";"):
        names = set() if vs == "base" else set(vs.split("+"))
        r = measure(args.arch, args.shape, names, probe_filter=args.probe)
        print(f"[{vs:24s}] t_c={r['t_compute']:.3f}s t_m={r['t_memory']:.3f}s "
              f"t_x={r['t_collective']:.3f}s bound={r['bottleneck']} "
              f"t_bound={r['t_bound']:.3f}s", flush=True)
        for name, mult, roof in r["details"]:
            print(f"    {name:26s} x{mult:3d} fl={roof.flops:.2e} "
                  f"by={roof.bytes_hbm:.2e} cl={roof.bytes_collective:.2e}")
        records.append({"arch": args.arch, "shape": args.shape, "variant": vs,
                        **{k: r[k] for k in ("t_compute", "t_memory",
                                             "t_collective", "t_bound",
                                             "bottleneck", "flops", "bytes",
                                             "coll")}})
    if args.out:
        with open(args.out, "a") as f:
            for rec in records:
                f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
