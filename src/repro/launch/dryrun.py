import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA", "--xla_force_host_platform_device_count=512")
)
# ^ MUST run before any other import (jax locks the device count on first
#   init).  Everything below this line may touch jax.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Each cell writes a JSON record with:
  * compiled.memory_analysis()  (per-device bytes: args/outputs/temps)
  * compiled.cost_analysis()    (per-device HLO FLOPs / bytes accessed)
  * per-collective operand bytes parsed from post-SPMD HLO
  * the roofline terms (repro.analysis.roofline)
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp


def run_cell(arch: str, shape: str, *, multi_pod: bool = False, overrides=None,
             mesh_override=None, sequence_parallel: bool = False, fsdp: bool = True,
             optimizer_name: str = "adamw", shampoo_sharded: bool = False,
             pure_dp=None, microbatches=None):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import canonical, get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import SHAPES, cell_applicable, input_specs
    from repro.launch.cache_specs import cache_partition_specs
    from repro.models import model_meta
    from repro.optim import adamw
    from repro.parallel.hints import hint_resolver
    from repro.parallel.sharding import make_policy, resolve_attn_mode, resolve_moe_mode
    from repro.train import make_train_step, make_prefill, make_serve_step
    from repro.analysis.collectives import collective_bytes_from_hlo
    from repro.analysis.hlo_walk import analyze_hlo
    from repro.analysis.roofline import roofline_terms

    arch = canonical(arch)
    if not cell_applicable(arch, shape):
        return {
            "arch": arch, "shape": shape, "multi_pod": multi_pod,
            "status": "skipped",
            "reason": "long_500k requires sub-quadratic serving state "
                      "(pure full-attention arch; see DESIGN.md §6)",
        }

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    info = SHAPES[shape]
    if mesh_override is not None:
        from repro.backend.compat import make_mesh

        shape_t = tuple(mesh_override)
        axes = ("pod", "data", "model")[-len(shape_t):]
        mesh = make_mesh(shape_t, axes)
        multi_pod = "pod" in axes
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    model_axis = mesh.shape["model"]
    n_chips = 1
    for a in mesh.axis_names:
        n_chips *= mesh.shape[a]
    if pure_dp is None:
        # Auto policy (§Perf): models <= 4B params train fastest as pure DP
        # over the whole mesh (no per-layer TP all-reduces) — measured 3-11x
        # on mamba2 / granite / musicgen.  Needs batch divisible by chips.
        pure_dp = (
            info["kind"] == "train"
            and cfg.param_counts()["total"] <= 4e9
            and info["batch"] % n_chips == 0
        )
    if microbatches is None:
        # Auto policy: gradient accumulation so big-TP train cells fit 16 GB
        # HBM (peak ~ 1/microbatches at +2.4% bound; measured on codeqwen).
        # Never under pure DP: slicing batch below one-per-chip idles chips.
        microbatches = (
            8
            if (info["kind"] == "train" and not pure_dp
                and cfg.param_counts()["total"] > 4e9)
            else 1
        )
    # Attention TP mode + flash chunk sizes follow the mesh (DESIGN.md §5).
    attn_over = {"attn_shard_mode": "none" if pure_dp else resolve_attn_mode(cfg, model_axis),
                 "moe_shard_mode": "tp" if pure_dp else resolve_moe_mode(cfg, model_axis)}
    if attn_over["attn_shard_mode"] == "cp" and info["kind"] != "decode":
        attn_over["attn_chunk"] = max(info["seq"] // model_axis, 128)
    cfg = dataclasses.replace(cfg, **attn_over)
    policy = make_policy(mesh, cfg, fsdp=fsdp, sequence_parallel=sequence_parallel,
                         pure_dp=pure_dp)
    dp = (("pod", "data", "model") if multi_pod else ("data", "model")) if pure_dp \
        else (("pod", "data") if multi_pod else ("data",))

    meta = model_meta(cfg, model_axis)
    param_sh = policy.param_shardings(meta)
    repl = NamedSharding(mesh, P())

    dp_total = 1
    for a in dp:
        dp_total *= mesh.shape[a]

    def batch_shardings(spec_tree):
        def one(s):
            use_dp = dp if (len(s.shape) and s.shape[0] % dp_total == 0) else None
            return NamedSharding(mesh, P(use_dp, *([None] * (len(s.shape) - 1))))
        return jax.tree_util.tree_map(one, spec_tree)

    if info["kind"] != "train":
        optimizer = None
    elif optimizer_name == "shampoo":
        from repro.optim import shampoo, ShampooOptions
        from repro.solver import EvdConfig

        optimizer = shampoo(3e-4, opts=ShampooOptions(
            block_size=256, update_interval=20, evd=EvdConfig(b=8, nb=64)))
    else:
        optimizer = adamw(3e-4)
    specs = input_specs(arch, shape, optimizer=optimizer, model_axis=model_axis, cfg=cfg)

    t0 = time.time()
    with hint_resolver(policy.resolver()):
        if info["kind"] == "train":
            step_fn = make_train_step(cfg, optimizer, microbatches=microbatches)
            # opt state: mu/nu mirror params; scalars replicate.
            if optimizer_name == "shampoo":
                flat_p = jax.tree_util.tree_leaves(param_sh)
                # mu/nu mirror params; stacked Kronecker stats replicate in
                # the paper-faithful baseline; the §Perf variant shards the
                # whole EVD batch over every mesh axis.
                axes = ("pod", "data", "model") if multi_pod else ("data", "model")
                stat_sh = (
                    NamedSharding(mesh, P(axes, None, None))
                    if shampoo_sharded else repl
                )
                opt_sh = type(specs["opt_state"])(
                    step=repl,
                    mu=jax.tree_util.tree_unflatten(
                        jax.tree_util.tree_structure(specs["opt_state"].mu), flat_p),
                    nu=jax.tree_util.tree_unflatten(
                        jax.tree_util.tree_structure(specs["opt_state"].nu), flat_p),
                    stats_l=stat_sh, stats_r=stat_sh, pre_l=stat_sh, pre_r=stat_sh,
                )
            else:
                opt_sh = type(specs["opt_state"])(
                    step=repl,
                    mu=param_sh,
                    nu=param_sh,
                )
            jitted = jax.jit(
                step_fn,
                in_shardings=(param_sh, opt_sh, batch_shardings(specs["batch"]), repl),
                out_shardings=(param_sh, opt_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(
                specs["params"], specs["opt_state"], specs["batch"],
                specs["step"],
            )
        elif info["kind"] == "prefill":
            fn = make_prefill(cfg)
            jitted = jax.jit(
                fn,
                in_shardings=(param_sh, batch_shardings(specs["batch"])),
            )
            lowered = jitted.lower(specs["params"], specs["batch"])
        else:
            fn = make_serve_step(cfg)
            cache_sh = cache_partition_specs(cfg, mesh, policy, specs["cache"])
            tok_dp = dp if specs["tokens"].shape[0] % dp_total == 0 else None
            tok_sh = NamedSharding(mesh, P(tok_dp, None))
            jitted = jax.jit(
                fn,
                in_shardings=(param_sh, cache_sh, tok_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(specs["params"], specs["cache"], specs["tokens"])

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = collective_bytes_from_hlo(hlo)
    walk = analyze_hlo(hlo, top=12)

    record = {
        "arch": arch,
        "shape": shape,
        "multi_pod": multi_pod,
        "status": "ok",
        "mesh": dict(zip(mesh.axis_names, [int(mesh.shape[a]) for a in mesh.axis_names])),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_estimate_bytes": int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            ),
        },
        "cost": {
            "flops_per_device": float(ca.get("flops", 0.0)),
            "bytes_accessed_per_device": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": colls,
        "walk": {
            "top_bytes": walk.get("top_bytes", []),
            "top_flops": walk.get("top_flops", []),
            "flops_per_device": walk["flops"],
            "hbm_bytes_per_device": walk["hbm_bytes"],
            "hbm_bytes_tpu_per_device": walk["hbm_bytes_tpu"],
            "collective_bytes_per_device": walk["collective_bytes"],
            "collectives": walk["collectives"],
            "unknown_trip_whiles": walk["unknown_trip_whiles"],
        },
    }
    record["roofline"] = roofline_terms(record, cfg, SHAPES[shape])
    print(f"[dryrun] {arch} x {shape} ({'2-pod' if multi_pod else '1-pod'}): "
          f"compile {t_compile:.0f}s, "
          f"{record['memory']['peak_estimate_bytes']/2**30:.2f} GiB/device, "
          f"{walk['flops']/1e9:.1f} GFLOP/device (walked), "
          f"coll {walk['collective_bytes']/2**20:.1f} MiB/device, "
          f"dominant {record['roofline']['dominant']}, "
          f"roofline_frac {record['roofline']['roofline_fraction']:.3f}")
    print("  memory_analysis:", ma)
    return record


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None, choices=list(
        ["train_4k", "prefill_32k", "decode_32k", "long_500k"]))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--all", action="store_true")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--smoke", action="store_true", help="use reduced configs")
    p.add_argument("--mesh", default=None,
                   help="debug mesh override, e.g. '2,4' or '2,2,4'")
    args = p.parse_args(argv)
    mesh_override = tuple(int(x) for x in args.mesh.split(",")) if args.mesh else None

    from repro.launch.specs import all_cells

    os.makedirs(args.out, exist_ok=True)
    cells = (
        [(a, s) for a, s, _ in all_cells()]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = 0
    for arch, shape in cells:
        tag = f"{arch}_{shape}_{'2pod' if args.multi_pod else '1pod'}"
        try:
            overrides = None
            rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                           overrides=overrides, mesh_override=mesh_override)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            traceback.print_exc()
            rec = {
                "arch": arch, "shape": shape, "multi_pod": args.multi_pod,
                "status": "error", "error": f"{type(e).__name__}: {e}",
            }
            failures += 1
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
