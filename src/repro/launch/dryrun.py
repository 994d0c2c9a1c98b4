import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh (16x16 single-pod / 2x16x16 multi-pod), print
memory_analysis / cost_analysis, and the FLOP / byte counts of the
partitioned HLO (trip-count-expanded; see hlo_analysis.py).

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch ... --multi-pod
"""

import argparse
import json
import time
import traceback


BASELINE_KNOBS = dict(microbatch=1, opt_dtype="f32", attn_chunk=1024,
                      fsdp_experts=True, shard_embed_vocab=True,
                      sp_attn=False, capacity_factor=1.25)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False, overrides: dict | None = None,
             tag: str = "", baseline: bool = False) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import SHAPES, get_config
    from ..models import get_model
    from ..train.step import make_train_step
    from ..serve.step import make_decode_step, make_prefill_step
    from . import hlo_analysis
    from .cells import skip_reason
    from .mesh import axis_sizes, dp_axes_of, make_production_mesh
    from .specs import input_specs, opt_state_pspecs, opt_state_specs

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    reason = skip_reason(arch, shape_name)
    if reason:
        rec = {"cell": cell_id, "status": "skipped", "reason": reason}
        _write(out_dir, cell_id, rec)
        print(json.dumps(rec))
        return rec

    t0 = time.time()
    cfg = get_config(arch)
    if baseline:  # pre-hillclimb knobs (§Perf baseline)
        cfg = cfg.replace(**BASELINE_KNOBS)
    if overrides:
        cfg = cfg.replace(**overrides)
    shp = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = axis_sizes(mesh)
    n_chips = int(mesh.devices.size)
    dp = dp_axes_of(mesh)
    model = get_model(cfg)

    params_abs = model.abstract_params()
    params_ps = model.pspecs(sizes)
    ns = lambda spec: jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                                   is_leaf=lambda x: isinstance(x, P))
    in_specs, in_ps = input_specs(arch, shape_name, axis_sizes=sizes, dp_axes=dp)

    if shp.kind == "train":
        import jax.numpy as jnp
        step = make_train_step(cfg, mesh, dp)
        opt_abs = opt_state_specs(
            params_abs, jnp.bfloat16 if cfg.opt_dtype == "bf16" else jnp.float32)
        opt_ps = opt_state_pspecs(params_ps)
        batch_abs = {k: v for k, v in in_specs.items()}
        batch_ps = {k: v for k, v in in_ps.items()}
        fn = jax.jit(step,
                     in_shardings=(ns(params_ps), ns(opt_ps), ns(batch_ps)),
                     out_shardings=(ns(params_ps), ns(opt_ps), None),
                     donate_argnums=(0, 1))
        lowered = fn.lower(params_abs, opt_abs, batch_abs)
    elif shp.kind == "prefill":
        step = make_prefill_step(cfg, shp.seq_len, mesh, dp)
        cache_abs = model.cache_defs(shp.global_batch, shp.seq_len)
        cache_ps = model.cache_pspecs(cache_abs, sizes, dp)
        fn = jax.jit(step,
                     in_shardings=(ns(params_ps), ns(in_ps)),
                     out_shardings=(None, ns(cache_ps)))
        lowered = fn.lower(params_abs, in_specs)
    else:  # decode
        step = make_decode_step(cfg, mesh, dp)
        cache_ps = in_ps["cache"]
        fn = jax.jit(step,
                     in_shardings=(ns(params_ps), ns(cache_ps),
                                   ns(in_ps["token"]), ns(in_ps["pos"])),
                     out_shardings=(None, ns(cache_ps)),
                     donate_argnums=(1,))
        lowered = fn.lower(params_abs, in_specs["cache"], in_specs["token"],
                           in_specs["pos"])
    t_lower = time.time() - t0

    t1 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    mem_rec = {}
    if mem is not None:
        for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            mem_rec[f] = getattr(mem, f, None)
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax: list with one dict
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    stats = hlo_analysis.analyze(hlo)

    rec = {
        "cell": cell_id, "status": "ok", "arch": arch, "shape": shape_name,
        "mesh": mesh_name, "chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_rec,
        "bytes_per_device": (mem_rec.get("argument_size_in_bytes") or 0) +
                            (mem_rec.get("temp_size_in_bytes") or 0),
        # minus the CPU-backend f32-upcast copies of bf16 scan state that a
        # TPU build (bf16-native MXU) would not materialize — see hlo_analysis
        "bytes_per_device_tpu_est": (mem_rec.get("argument_size_in_bytes") or 0) +
                                    (mem_rec.get("temp_size_in_bytes") or 0) -
                                    stats.get("upcast_artifact_bytes", 0),
        "cost_analysis_flops_unscaled": cost.get("flops"),
        "hlo_stats": {k: v for k, v in stats.items() if k != "trip_counts"},
        "trip_counts": stats["trip_counts"],
        "overrides": overrides or {},
    }
    if save_hlo:
        with open(os.path.join(out_dir, cell_id + ".hlo.txt"), "w") as f:
            f.write(hlo)
    _write(out_dir, cell_id, rec)
    print(json.dumps({k: rec[k] for k in
                      ("cell", "status", "compile_s", "bytes_per_device",
                       "bytes_per_device_tpu_est")}))
    return rec


def _write(out_dir, cell_id, rec):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--baseline", action="store_true",
                    help="pin pre-hillclimb perf knobs")
    ap.add_argument("--override", default="",
                    help="comma k=v model-config overrides (perf experiments)")
    args = ap.parse_args()
    overrides = {}
    for kv in args.override.split(","):
        if kv:
            k, v = kv.split("=")
            overrides[k] = type_guess(v)
    try:
        run_cell(args.arch, args.shape, args.multi_pod, args.out,
                 args.save_hlo, overrides or None, args.tag, args.baseline)
    except Exception:
        rec = {"cell": f"{args.arch}__{args.shape}", "status": "error",
               "error": traceback.format_exc()}
        mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
        _write(args.out, f"{args.arch}__{args.shape}__{mesh_name}" +
               (f"__{args.tag}" if args.tag else ""), rec)
        print(rec["error"])
        raise SystemExit(1)


def type_guess(v: str):
    for t in (int, float):
        try:
            return t(v)
        except ValueError:
            pass
    return v


if __name__ == "__main__":
    main()
