"""Training driver (CPU-runnable with tiny/reduced configs; the full-size
configs are exercised by the dry-run).

  python -m repro.launch.train --arch yi-6b --tiny --steps 50 \
      --global-batch 8 --seq-len 64 [--icheck] [--resize-at 30 --ranks 2]

With --icheck, the run is driven by the ElasticTrainer: full paper
Listing 1 control flow (register -> add_adapt -> commit/async -> probe ->
redistribute on resize), backed by an in-process iCheck cluster.
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--full", dest="tiny", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--icheck", action="store_true")
    ap.add_argument("--commit-every", type=int, default=10)
    ap.add_argument("--resize-at", type=int, default=0,
                    help="inject an RM resize event at this step")
    ap.add_argument("--ranks", type=int, default=1)
    ap.add_argument("--new-ranks", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    import jax

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticLMData
    from repro.optim import AdamWConfig, warmup_cosine
    from repro.train import make_train_state, make_train_step

    cfg = get_config(args.arch, tiny=args.tiny)
    shape = ShapeConfig("cli", "train", args.seq_len, args.global_batch)
    opt_cfg = AdamWConfig(lr=args.lr)

    if args.icheck:
        from repro.core import ICheckCluster
        from repro.train import ElasticTrainer

        with ICheckCluster(n_icheck_nodes=2) as cluster:
            trainer = ElasticTrainer(
                cfg, shape, cluster, ranks=args.ranks, seed=args.seed,
                opt_cfg=opt_cfg, commit_every=args.commit_every,
                total_steps=args.steps)
            if args.resize_at:
                first = trainer.run(args.resize_at)
                cluster.rm.schedule_resize("train", args.new_ranks)
                rest = trainer.run(args.steps - args.resize_at)
                print(f"[resize] {args.ranks} -> {args.new_ranks} ranks, "
                      f"resizes={trainer.resizes}")
            else:
                rest = trainer.run(args.steps)
            trainer.finalize()
            for m in trainer.metrics_log[:3] + trainer.metrics_log[-3:]:
                print(f"step {m['step']:5d} loss {m['loss']:.4f}")
            print(f"final loss {rest['final_loss']:.4f} "
                  f"({rest['wall_s']:.1f}s)")
        return

    key = jax.random.key(args.seed)
    state = make_train_state(cfg, key, opt_cfg)
    schedule = warmup_cosine(args.lr, warmup=10, total=args.steps)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, schedule,
                                      microbatches=args.microbatches),
                      donate_argnums=0)
    data = SyntheticLMData(cfg, shape, seed=args.seed)
    t0 = time.monotonic()
    for i in range(args.steps):
        batch = data.next_batch()
        state, metrics = step_fn(state, batch)
        if i < 3 or i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
    dt = time.monotonic() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({args.steps * shape.global_batch * shape.seq_len / dt:.0f} tok/s)")


if __name__ == "__main__":
    main()
