"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 chipbench/calibrate.py --config <name> --seeds 12 --controls 3

In one process: the trainer of the cell is built once, and for each seed
the benchmark's weights are handed to it, it takes the cell's three
verified steps through its own call, and the plain reference follows.
The program's gaps to the reference over the seeds give each number's
lower reading.  On the first ``--controls`` seeds the reference is also
put in the program's place one precision down (fp8 matmul operands for a
bf16 configuration) and with a planted fault (half of the batch left out);
their gaps give the upper readings.  The q8 codec's control (4-bit codes
in its place) and the raw codec's (the state rounded to bf16) are read on
the program's state after its steps.  One JSON line per seed, then a
summary, on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROL_DTYPE = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def codec_controls(d) -> dict:
    """Each codec's control on the program's current state."""
    import jax.numpy as jnp

    from chipbench import checks

    worst, differ = 0.0, 0
    for name, x in d.named_state().items():
        if not jnp.issubdtype(x.dtype, jnp.floating):
            continue
        worst = max(worst, checks.half_scale_excess(
            x, checks.int4_roundtrip(x)))
        y = x.astype(jnp.bfloat16).astype(x.dtype)
        differ += checks.count_differing(checks.fingerprints({name: x}),
                                         checks.fingerprints({name: y}))
    return {"half_scale_ratio": worst, "leaves_differing": differ}


def calibrate(cfg: dict, traffic: dict, family, seeds, controls: int,
              emit=print) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench.driver import Driver
    from chipbench.spans import Spans

    d = Driver(cfg, traffic, family, seeds[0], Spans())
    d.cluster = d.make_cluster()
    d.trainer = d.new_trainer()
    low = jnp.dtype(CONTROL_DTYPE[cfg["compute_dtype"]])
    rows = cfg["global_batch"] // 2
    per_seed = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        d.seed = seed
        d.losses, d.readings = [], {}
        d.inject(d.trainer)
        for k in range(traffic["verified_steps"]):
            d._step(d.trainer)
            if k == 0:
                d.readings["grad_norms"] = d._grad_norms()
        d.readings["change_norms"] = d._change_norms()
        d.readings["losses"] = list(d.losses)
        rec = {"seed": seed}
        if i < controls:
            rec["codec_control"] = codec_controls(d)
        # free the program's state before the reference runs (the deleted
        # leaves keep their shapes for the next seed's inject)
        for leaf in jax.tree.leaves(d.trainer.state):
            leaf.delete()
        ref = d.reference()
        rec["program"] = d.compare(ref)
        rec["losses"] = {"program": d.readings["losses"],
                         "reference": ref["losses"]}
        if i < controls:
            for tag, kw in (("control", {"dtype": low}),
                            ("half_batch", {"rows": rows})):
                other = d.reference(**kw)
                saved = d.readings
                d.readings = other
                rec[tag] = d.compare(ref)
                d.readings = saved
        rec["seconds"] = time.perf_counter() - t0
        emit(json.dumps(rec))
        per_seed.append(rec)
    d.cluster.close()
    return summarize(per_seed)


def summarize(per_seed) -> dict:
    out = {}
    for key in ("loss_gap", "grad_gap", "change_gap"):
        progs = [r["program"][key] for r in per_seed]
        out[key] = {"lower": max(progs), "seeds": len(progs)}
        for tag in ("control", "half_batch"):
            vals = [r[tag][key] for r in per_seed if tag in r]
            if vals:
                out[key][tag] = min(vals)
    cc = [r["codec_control"] for r in per_seed if "codec_control" in r]
    if cc:
        out["codec_control"] = {k: min(c[k] for c in cc) for k in cc[0]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="q8delta-save")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU: the readings are taken on the chip only")
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench.harness import HERE, load_json, load_module

    cfg = load_json(os.path.join(HERE, "configs", args.config + ".json"))
    traffic = load_json(os.path.join(HERE, "traffic", args.traffic + ".json"))
    family = load_module(os.path.join(HERE, "models", cfg["family"] + ".py"),
                         cfg["family"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    summary = calibrate(cfg, traffic, family, seeds, args.controls)
    print(json.dumps({"summary": summary, "config": args.config}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
