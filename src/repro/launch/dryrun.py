import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove every (architecture x input shape x mesh) cell
lowers, SPMD-partitions and compiles on the production mesh, and extract
the roofline terms from the compiled artifact.

MUST set XLA_FLAGS before any other import (jax locks the device count on
first init) -- hence the module's first two lines.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --arch yi-6b            # all shapes
  python -m repro.launch.dryrun --all                   # all 10 archs
  ... [--multipod] [--microbatches N] [--rules tp|fsdp] [--out artifacts/]
"""
import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402


def lower_cell(cfg, shape, mesh, *, rules=None, opt_cfg=None,
               microbatches=1, donate=True, extra_tag=""):
    """Lower + compile one cell; returns the artifact dict."""
    import jax
    from repro.launch import specs as S
    from repro.launch.hlo import analyze, roofline_terms
    from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS
    from repro.models import decode_step, prefill
    from repro.sharding import use_rules
    from repro.train import make_train_step

    rules = rules or S.cell_rules(cfg, shape, mesh)
    if microbatches == 0:          # auto
        microbatches = S.default_microbatches(cfg, shape, mesh)
    in_specs = S.input_specs(cfg, shape, opt_cfg)
    in_sh = S.cell_shardings(cfg, shape, mesh, rules, opt_cfg)

    if shape.kind == "train":
        step = make_train_step(cfg, opt_cfg, microbatches=microbatches)

        def fn(state, batch):
            with mesh, use_rules(mesh, rules):
                return step(state, batch)

        jfn = jax.jit(fn, in_shardings=in_sh,
                      out_shardings=(in_sh[0], None),
                      donate_argnums=(0,) if donate else ())
    elif shape.kind == "prefill":
        def fn(params, batch, cache):
            with mesh, use_rules(mesh, rules):
                return prefill(cfg, params, batch, cache)

        jfn = jax.jit(fn, in_shardings=in_sh,
                      out_shardings=(None, in_sh[2]),
                      donate_argnums=(2,) if donate else ())
    else:
        def fn(params, cache, tokens):
            with mesh, use_rules(mesh, rules):
                return decode_step(cfg, params, cache, tokens)

        jfn = jax.jit(fn, in_shardings=in_sh,
                      out_shardings=(None, in_sh[1]),
                      donate_argnums=(1,) if donate else ())

    t0 = time.monotonic()
    lowered = jfn.lower(*in_specs)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    analysis = analyze(hlo_text)
    if os.environ.get("REPRO_DRYRUN_TOPS"):
        from repro.launch.hlo import top_instructions
        tops = top_instructions(hlo_text, k=10)
        for cat in ("bytes", "collectives", "flops"):
            print(f"  --- top {cat} ---")
            for v, comp, line in tops[cat]:
                print(f"   {v:.3e}  {comp[:36]:36s} {line[:130]}")
    coll = analysis["collectives"]
    n_chips = mesh.devices.size
    terms = roofline_terms(analysis, PEAK_FLOPS, HBM_BW, ICI_BW)

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2 * n_active * tokens
    model_flops_per_chip = model_flops / n_chips
    useful = model_flops_per_chip / terms["flops"] if terms["flops"] else 0.0
    roofline_frac = (model_flops_per_chip / PEAK_FLOPS) / terms["bound_s"] \
        if terms["bound_s"] > 0 else 0.0

    art = {
        "arch": cfg.name, "shape": shape.name, "kind": shape.kind,
        "mesh": dict(mesh.shape), "chips": int(n_chips),
        "tag": extra_tag, "microbatches": microbatches,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
        },
        "cost": {"flops": terms["flops"], "bytes": terms["bytes"],
                 "xla_flops_body_once": float(xla_cost.get("flops", 0.0)),
                 "xla_bytes_body_once": float(
                     xla_cost.get("bytes accessed", 0.0))},
        "collectives": coll,
        "roofline": {
            "t_compute": terms["t_compute"],
            "t_memory": terms["t_memory"],
            "t_collective": terms["t_collective"],
            "dominant": terms["dominant"],
            "bound_s": terms["bound_s"],
            "model_flops": model_flops,
            "model_flops_per_chip": model_flops_per_chip,
            "useful_flop_ratio": useful,
            "roofline_fraction": roofline_frac,
        },
        "params": {"total": n_params, "active": n_active},
    }
    return art


def run_cell(arch, shape_name, multipod, microbatches=0, rules_name=None,
             out_dir=None, tag="", kv_quant=False, remat=None):
    import dataclasses

    import jax  # noqa: F401
    from repro.configs import get_config, get_shape
    from repro.launch.mesh import make_production_mesh
    from repro.sharding import get_rules

    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if remat:
        cfg = dataclasses.replace(cfg, remat_policy=remat)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multipod)
    rules = get_rules(rules_name) if rules_name else None
    art = lower_cell(cfg, shape, mesh, rules=rules,
                     microbatches=microbatches, extra_tag=tag)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        pod = "multipod" if multipod else "pod"
        fn = os.path.join(out_dir, f"{arch}__{shape_name}__{pod}"
                          + (f"__{tag}" if tag else "") + ".json")
        with open(fn, "w") as f:
            json.dump(art, f, indent=1)
    return art


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = auto (fit HBM)")
    ap.add_argument("--rules", default=None)
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (perf variant H8)")
    ap.add_argument("--remat", default=None,
                    help="override remat policy: full|dots|psum|none")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args(argv)

    from repro.configs import ARCH_IDS, get_config, shapes_for

    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = [s.name for s in shapes_for(cfg)] if not args.shape \
            else [args.shape]
        for shape_name in shapes:
            meshes = [False, True] if args.both_meshes else [args.multipod]
            for mp in meshes:
                label = f"{arch} x {shape_name} x {'2x16x16' if mp else '16x16'}"
                try:
                    art = run_cell(arch, shape_name, mp,
                                   microbatches=args.microbatches,
                                   rules_name=args.rules, out_dir=args.out,
                                   tag=args.tag, kv_quant=args.kv_quant,
                                   remat=args.remat)
                    r = art["roofline"]
                    print(f"[OK] {label}: compile={art['compile_s']}s "
                          f"mem/dev={art['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
                          f"dominant={r['dominant']} "
                          f"roofline={r['roofline_fraction']:.3f}",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    failures.append((label, repr(e)))
                    traceback.print_exc()
                    print(f"[FAIL] {label}: {e}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for label, err in failures:
            print(f"  {label}: {err}")
        sys.exit(1)
    print("\nALL CELLS PASS")


if __name__ == "__main__":
    main()
