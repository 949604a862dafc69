import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# Placeholder host devices exist ONLY for this dry-run entry point.
#
# Second flag: XLA:CPU's while-loop-invariant-code-motion hoists the
# backward-pass bf16->f32 convert of the SAVED-ACTIVATION stack out of the
# layer loop, materializing a duplicate f32 copy of all remat checkpoints
# (~2x activation memory, CPU-backend artifact — XLA:TPU buffer assignment
# is HBM-aware). Disable it so memory_analysis reflects the real plan.
os.environ["XLA_FLAGS"] += " --xla_disable_hlo_passes=while-loop-invariant-code-motion"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each runnable cell this proves the distribution config is coherent
without hardware: jit(step).lower(**input_specs).compile() must succeed on
the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh. We record
memory_analysis (fits-per-device), XLA cost_analysis, and our own
trip-count-corrected HLO cost model (launch/hlo_cost.py) for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--out-dir experiments/dryrun]
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


# Baseline grad-accumulation factors chosen so the per-device saved-
# activation floor (L x B_local x S x D x 2B for remat-per-layer) fits HBM.
# Recorded with each cell; hillclimbing may revisit.
DEFAULT_MICROBATCH = {
    "deepseek-67b": 16, "internvl2-76b": 16, "falcon-mamba-7b": 4,
    "zamba2-2.7b": 4, "phi3-mini-3.8b": 2, "qwen2-moe-a2.7b": 4,
    "granite-moe-1b-a400m": 2, "hubert-xlarge": 2, "gemma-2b": 2,
    "qwen2-1.5b": 2,
}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatch: int = 0, overrides: str = "",
             compress: bool = False) -> dict:
    import jax
    from repro.configs.registry import get_config, sub_quadratic
    from repro.configs.shapes import SHAPES, cell_is_runnable
    from repro.launch import hlo_cost, steps as St
    from repro.launch.mesh import make_production_mesh
    from repro.optim import adamw

    cfg = get_config(arch)
    if overrides:
        import dataclasses
        kv = dict(item.split("=", 1) for item in overrides.split(","))
        typed = {}
        for k, v in kv.items():
            cur = getattr(cfg, k)
            typed[k] = type(cur)(eval(v)) if not isinstance(cur, str) else v
        cfg = dataclasses.replace(cfg, **typed)
    shape = SHAPES[shape_name]
    ok, reason = cell_is_runnable(cfg.family, shape, sub_quadratic(cfg))
    result = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "family": cfg.family}
    if not ok:
        result.update(status="skipped", reason=reason)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    if microbatch == 0 and shape.kind == "train":
        microbatch = DEFAULT_MICROBATCH.get(arch, 1)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = adamw.OptConfig()
            step, _ = St.make_train_step(
                cfg, opt, mesh, shape=shape,
                microbatch=microbatch if microbatch > 1 else None,
                compress=dict(k=512) if compress else None)
            state_shapes, _ = St.abstract_state(cfg)
            lowered = step.lower(state_shapes, St.input_specs(cfg, shape))
        elif shape.kind == "prefill":
            step, _ = St.make_prefill_step(cfg, mesh, shape=shape)
            params_shapes, _ = St.abstract_params(cfg)
            lowered = step.lower(params_shapes, St.input_specs(cfg, shape))
        else:  # decode
            step, _, _ = St.make_serve_step(cfg, shape, mesh)
            params_shapes, _ = St.abstract_params(cfg)
            cache_shapes = St.cache_abstract(cfg, shape)
            lowered = step.lower(params_shapes,
                                 St.input_specs(cfg, shape)["tokens"],
                                 cache_shapes,
                                 jax.ShapeDtypeStruct((), jax.numpy.int32))
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    mine = hlo_cost.analyze(hlo)
    print(f"[{arch} x {shape_name} x {result['mesh']}] "
          f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
    print("memory_analysis:", {
        k: getattr(mem, k, None) for k in
        ("argument_size_in_bytes", "output_size_in_bytes",
         "temp_size_in_bytes", "generated_code_size_in_bytes")})
    print("cost_analysis flops (uncorrected):", cost.get("flops"))
    print("hlo_cost (trip-corrected):", {k: v for k, v in mine.items()
                                         if k != "coll_ops"})
    print("collectives:", mine["coll_ops"])

    result.update(
        status="ok",
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory={k: int(getattr(mem, k, 0) or 0) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")},
        xla_cost={k: cost.get(k) for k in ("flops", "bytes accessed",
                                           "transcendentals")
                  if k in cost},
        hlo_cost=mine,
        microbatch=microbatch, overrides=overrides, compress=compress,
    )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--overrides", default="",
                    help="cfg overrides k=v,k=v (perf iterations)")
    ap.add_argument("--compress", action="store_true",
                    help="sampled cross-pod gradient exchange (train cells)")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()

    if args.all:
        from repro.configs.registry import list_archs
        from repro.configs.shapes import SHAPES
        os.makedirs(args.out_dir, exist_ok=True)
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        jobs = [(a, s, mp) for a in list_archs() for s in SHAPES
                for mp in meshes]
        failures = 0
        for a, s, mp in jobs:
            tag = f"{a}__{s}__{'mp' if mp else 'sp'}"
            out = os.path.join(args.out_dir, tag + ".json")
            if os.path.exists(out):
                print("skip (exists):", tag)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--out", out]
            if mp:
                cmd.append("--multi-pod")
            print(">>>", tag, flush=True)
            try:
                r = subprocess.run(cmd, timeout=args.timeout)
                if r.returncode != 0:
                    failures += 1
            except subprocess.TimeoutExpired:
                failures += 1
                with open(out, "w") as f:
                    json.dump({"arch": a, "shape": s,
                               "mesh": "2x16x16" if mp else "16x16",
                               "status": "timeout"}, f)
        print("done; failures:", failures)
        sys.exit(1 if failures else 0)

    try:
        result = run_cell(args.arch, args.shape, args.multi_pod,
                          args.microbatch, args.overrides, args.compress)
    except Exception:
        result = {"arch": args.arch, "shape": args.shape,
                  "mesh": "2x16x16" if args.multi_pod else "16x16",
                  "status": "error", "error": traceback.format_exc()}
        print(result["error"], file=sys.stderr)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    sys.exit(0 if result.get("status") in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
