"""Save -> kill -> resume on one TPU chip at the published widths of qwen2.5-3b.

Drives the checkpoint service the way a training job does: an
``ElasticTrainer`` steps the model and saves asynchronously through an
``ICheckCluster`` (with q8-delta the ``ckpt_codec`` Pallas kernels encode on
the chip before the device-to-host copy), the job is killed, and a new
trainer restores from the agents and trains on.

  python chip_smoke.py              # one chip: phases A, B, C
  python chip_smoke.py --chips 4    # four chips: the resize phase only

Phases on one chip:
  A  reference: K+M steps, no saves.
  B  raw: K steps with async raw saves, kill, resume.  The next M losses and
     the final state are bit-identical to A's.
  C  q8-delta: K steps with a save every 2 steps (a keyframe, then delta
     frames), kill, resume.  Every float leaf is within half a block scale
     of the state at step K, integer leaves and the data cursor are exact,
     and the next M losses stay within LOSS_BAND of A's.
With --chips 4: train on 2 ranks with async raw saves, resize to 4 ranks and
back to 2; after each resize every device holds the committed state bit for
bit, and the steps after it run.

Lines before the last are single-run observations, not metrics.  The last
line is {"ok": true, "device": {...}}.  A failed check, or a run in which
JAX finds no TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import hashlib
import json
import os
import resource
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import TRAIN_4K, ShapeConfig  # noqa: E402
from repro.core import ICheckCluster  # noqa: E402
from repro.core import events as E  # noqa: E402
from repro.core.snapshot import leaf_names  # noqa: E402
from repro.kernels.ckpt_codec.blocks import BLOCK  # noqa: E402
from repro.kernels.common import resolve_impl  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.train import ElasticTrainer  # noqa: E402
from repro.train.state import make_train_state  # noqa: E402

ARCH = "qwen2.5-3b"
# depth and vocabulary cut to one chip's share; every width as published
CUTS = {"num_layers": 4, "vocab_size": 37984}
SEQ_LEN = TRAIN_4K.seq_len
GLOBAL_BATCH = 2          # 4 leaves no HBM for the q8 codes beside the step
K, M = 6, 4               # steps before the kill, steps after the resume
RAW_SAVE_EVERY = 3
Q8_SAVE_EVERY = 2
SEED = 0
OPT = AdamWConfig()
# |loss_C - loss_A| <= LOSS_BAND * loss_A on each of the M steps after the
# resume.  q8 moves each value by at most absmax/254 of its block, which
# shifts the next losses by a fraction that shrinks with width: on the CPU
# (xla kernels, 4 seeds) at most 0.35 % at d_model 64 and 0.05 % at d_model
# 256.  1 % covers that and still fails a resume that lost a region, scaled
# one wrongly or replayed a broken chain (those move the loss by whole units
# or make it non-finite).
LOSS_BAND = 0.01


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def chip_config(global_batch: int = GLOBAL_BATCH):
    published = get_config(ARCH)
    for key, cut in CUTS.items():
        print(f"cut {key}: {cut} (published {getattr(published, key)})")
    print(f"seq_len {SEQ_LEN} (train_4k {TRAIN_4K.seq_len}), global batch "
          f"{global_batch} (train_4k {TRAIN_4K.global_batch})")
    cfg = dataclasses.replace(published, **CUTS)
    return cfg, ShapeConfig("chip_smoke", "train", SEQ_LEN, global_batch)


# --------------------------------------------------------------- helpers
class Observer:
    """Wall times of one phase: steps, commit enqueue and completion."""

    def __init__(self, cluster):
        self.step_s: list = []
        self.commits: dict = {}       # ckpt id -> record
        self._unsub = cluster.bus.subscribe(self._on_done,
                                            events=(E.COMMIT_DONE,))

    def _on_done(self, ev) -> None:
        rec = self.commits.setdefault(ev.payload["ckpt"], {})
        rec["done_t"] = time.perf_counter()
        rec["bytes"] = ev.payload["bytes"]

    def summary(self) -> dict:
        self._unsub()
        out = {"first_step_s": self.step_s[0] if self.step_s else None,
               "step_median_s": statistics.median(self.step_s[1:])
               if len(self.step_s) > 1 else None, "commits": []}
        for cid, rec in sorted(self.commits.items()):
            out["commits"].append({
                "ckpt": cid, "step": rec.get("step"),
                "bytes": rec.get("bytes"),
                "enqueue_s": rec.get("enqueue_s"),
                "complete_s": rec["done_t"] - rec["t0"]
                if "done_t" in rec and "t0" in rec else None})
        return out


def make_trainer(cluster, cfg, shape, *, app_id, codec="raw", ranks=1,
                 impl=None):
    return ElasticTrainer(cfg, shape, cluster, app_id=app_id, ranks=ranks,
                          seed=SEED, opt_cfg=OPT, commit_every=0,
                          probe_every=0, codec=codec, total_steps=1000,
                          impl=impl)


def run_steps(trainer, n: int, obs: Observer, save_every: int = 0) -> list:
    """Step ``n`` times, committing asynchronously every ``save_every``
    steps; returns the commit handles."""
    handles = []
    for _ in range(n):
        t0 = time.perf_counter()
        trainer.run(1)
        obs.step_s.append(time.perf_counter() - t0)
        step = trainer.metrics_log[-1]["step"]
        if save_every and step % save_every == 0:
            t0 = time.perf_counter()
            h = trainer.commit()
            rec = obs.commits.setdefault(h.ckpt_id, {})
            rec.update(t0=t0, step=step,
                       enqueue_s=time.perf_counter() - t0)
            handles.append(h)
    return handles


def kill(trainer) -> None:
    """The job dies: its device memory goes with it, nothing is finalized."""
    for leaf in jax.tree.leaves(trainer.state):
        leaf.delete()
    trainer.state = None


def host_state(state) -> dict:
    return {name: np.asarray(leaf)
            for name, leaf in zip(leaf_names(state), jax.tree.leaves(state))}


def digests(state) -> dict:
    return {name: hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()
            for name, leaf in zip(leaf_names(state), jax.tree.leaves(state))}


def half_scale_excess(saved: np.ndarray, restored: np.ndarray,
                      chunk: int = 1 << 22) -> float:
    """Largest ``|restored - saved| / (absmax/254 + ulp(absmax))`` over the
    256-value blocks of one leaf: <= 1 is within half a block scale.
    Exact (float64), a chunk of blocks at a time."""
    x = np.ravel(saved)
    y = np.ravel(restored)
    worst = 0.0
    for lo in range(0, x.size, chunk):
        xs = x[lo:lo + chunk].astype(np.float64)
        ys = y[lo:lo + chunk].astype(np.float64)
        pad = (-xs.size) % BLOCK
        xb = np.pad(xs, (0, pad)).reshape(-1, BLOCK)
        diff = np.abs(np.pad(ys, (0, pad)).reshape(-1, BLOCK) - xb)
        absmax = np.max(np.abs(xb), axis=1, keepdims=True)
        bound = absmax / 254 + np.spacing(absmax.astype(np.float32))
        worst = max(worst, float(np.max(diff / bound)))
    return worst


def peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def cluster_for(cfg) -> ICheckCluster:
    """Agent memory is host RAM here: room for four raw checkpoints a node
    (a node may hold every shard of a checkpoint, and L1 lets one go only
    once its drain to the PFS has landed)."""
    shapes = jax.eval_shape(lambda k: make_train_state(cfg, k, OPT),
                            jax.random.key(SEED))
    state_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                      for s in jax.tree.leaves(shapes))
    return ICheckCluster(n_icheck_nodes=2, node_memory=4 * state_bytes)


def free(*trainers) -> None:
    for t in trainers:
        if t.state is not None:
            kill(t)
    gc.collect()


def report(phase: str, **fields) -> None:
    fields["host_peak_rss_bytes"] = peak_rss()
    print(f"phase {phase}: " + json.dumps(fields, default=float), flush=True)


# ---------------------------------------------------------------- phases
def phase_reference(cfg, shape, *, k=K, m=M, impl=None) -> dict:
    """A: K+M steps with no saves; the losses and final-state digests."""
    with cluster_for(cfg) as cluster:
        obs = Observer(cluster)
        t0 = time.perf_counter()
        tr = make_trainer(cluster, cfg, shape, app_id="ref", impl=impl)
        init_s = time.perf_counter() - t0
        run_steps(tr, k + m, obs)
        losses = [r["loss"] for r in tr.metrics_log]
        dig = digests(tr.state)
        tr.finalize()
        free(tr)
    check(all(np.isfinite(losses)), f"A: non-finite loss {losses}")
    report("A", init_s=init_s, losses=losses, **obs.summary())
    return {"losses": losses, "digests": dig}


def phase_raw(cfg, shape, ref: dict, *, k=K, m=M, impl=None) -> dict:
    """B: async raw saves, kill at K, resume; bit-identical to A."""
    with cluster_for(cfg) as cluster:
        obs = Observer(cluster)
        tr = make_trainer(cluster, cfg, shape, app_id="job", impl=impl)
        for h in run_steps(tr, k, obs, save_every=RAW_SAVE_EVERY):
            h.wait(timeout=300)
        kill(tr)
        t0 = time.perf_counter()
        tr2 = make_trainer(cluster, cfg, shape, app_id="job", impl=impl)
        resume_s = time.perf_counter() - t0
        check(tr2.restarted and int(tr2.state.step) == k,
              f"B: resumed={tr2.restarted} at step {int(tr2.state.step)}, "
              f"want {k}")
        run_steps(tr2, m, obs)
        losses = [r["loss"] for r in tr2.metrics_log]
        same_losses = losses == ref["losses"][k:k + m]
        dig = digests(tr2.state)
        same_state = dig == ref["digests"]
        tr2.finalize()
        drains = cluster.controller.wait_for_drains(timeout=600)
        free(tr2, tr)
    report("B", resume_s=resume_s, losses=losses,
           bit_identical_losses=same_losses, bit_identical_state=same_state,
           drains_ok=drains.get("ok", True), **obs.summary())
    check(same_losses, f"B: losses {losses} != A {ref['losses'][k:k + m]}")
    diff = sorted(n for n in dig if dig[n] != ref["digests"].get(n))
    check(same_state, f"B: final state differs from A in {diff}")
    return {"losses": losses, "bit_identical": same_losses and same_state}


def phase_q8_delta(cfg, shape, ref: dict, *, k=K, m=M, impl=None) -> dict:
    """C: q8-delta saves every 2 steps, kill at K, resume within the
    half-scale bound, then M steps within LOSS_BAND of A."""
    with cluster_for(cfg) as cluster:
        obs = Observer(cluster)
        frames = []
        unsub = cluster.bus.subscribe(
            lambda ev: frames.append((ev.payload["key_frames"],
                                      ev.payload["delta_frames"])),
            events=(E.CKPT_DELTA_COMMITTED,))
        tr = make_trainer(cluster, cfg, shape, app_id="job",
                          codec="q8-delta", impl=impl)
        handles = run_steps(tr, k, obs, save_every=Q8_SAVE_EVERY)
        saved = host_state(tr.state)
        saved_data = tr.data.state_array().copy()
        for h in handles:
            h.wait(timeout=300)
        unsub()
        kill(tr)
        meta, level = cluster.controller.latest_restartable("job")
        chain = max(len(r.chain or ()) for r in meta.regions.values())
        t0 = time.perf_counter()
        tr2 = make_trainer(cluster, cfg, shape, app_id="job",
                           codec="q8-delta", impl=impl)
        resume_s = time.perf_counter() - t0
        check(tr2.restarted and int(tr2.state.step) == k,
              f"C: resumed={tr2.restarted} at step {int(tr2.state.step)}, "
              f"want {k}")
        excess, exact = {}, {}
        for name, leaf in zip(leaf_names(tr2.state),
                              jax.tree.leaves(tr2.state)):
            a, b = saved.pop(name), np.asarray(leaf)
            if jax.dtypes.issubdtype(a.dtype, np.floating):
                excess[name] = half_scale_excess(a, b)
            else:
                exact[name] = np.array_equal(a, b)
        data_exact = np.array_equal(tr2.data.state_array(), saved_data)
        run_steps(tr2, m, obs)
        losses = [r["loss"] for r in tr2.metrics_log]
        ref_losses = ref["losses"][k:k + m]
        rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
        tr2.finalize()
        drains = cluster.controller.wait_for_drains(timeout=600)
        free(tr2, tr)
    worst = max(excess, key=excess.get)
    report("C", resume_s=resume_s, restored_from=level,
           frames_per_commit=frames, longest_chain=chain,
           worst_half_scale_ratio=excess[worst], worst_leaf=worst,
           int_leaves_exact=all(exact.values()), data_cursor_exact=data_exact,
           losses=losses, loss_rel_diff=rel, loss_band=LOSS_BAND,
           drains_ok=drains.get("ok", True), **obs.summary())
    check(chain >= 3, f"C: longest delta chain {chain}, want key + 2 deltas")
    check(excess[worst] <= 1.0,
          f"C: {worst} off by {excess[worst]:.3f} x the half-scale bound")
    check(all(exact.values()),
          f"C: integer leaves differ: {[n for n, v in exact.items() if not v]}")
    check(data_exact, "C: data-iterator state differs")
    check(all(r <= LOSS_BAND for r in rel),
          f"C: losses {losses} outside {LOSS_BAND} of A {ref_losses}")
    return {"losses": losses, "worst_half_scale_ratio": excess[worst],
            "longest_chain": chain}


def phase_resize(cfg, shape, *, impl=None) -> dict:
    """Four chips: 2 ranks with async raw saves, resize to 4 and back to 2.
    After each resize every device holds the committed state bit for bit."""
    with cluster_for(cfg) as cluster:
        obs = Observer(cluster)
        tr = make_trainer(cluster, cfg, shape, app_id="elastic", ranks=2,
                          impl=impl)
        handles = run_steps(tr, 2, obs, save_every=2)
        log = []
        for new_ranks in (4, 2):
            committed = digests(tr.state)
            cluster.rm.schedule_resize("elastic", new_ranks)
            t0 = time.perf_counter()
            check(tr.maybe_adapt(), f"resize to {new_ranks} did not run")
            resize_s = time.perf_counter() - t0
            names = leaf_names(tr.state)
            leaves = jax.tree.leaves(tr.state)
            n_dev = {len(leaf.sharding.device_set) for leaf in leaves}
            check(n_dev == {new_ranks},
                  f"after resize to {new_ranks}: leaves on {n_dev} devices")
            bad = [(name, str(sh.device)) for name, leaf in zip(names, leaves)
                   for sh in leaf.addressable_shards
                   if hashlib.sha256(np.asarray(sh.data).tobytes())
                   .hexdigest() != committed[name]]
            check(not bad, f"after resize to {new_ranks}: differs on {bad[:5]}")
            run_steps(tr, 2, obs)
            loss = tr.metrics_log[-1]["loss"]
            check(np.isfinite(loss), f"loss {loss} after resize")
            log.append({"ranks": new_ranks, "devices": sorted(n_dev),
                        "resize_s": resize_s, "identical_on_every_device": True,
                        "loss_after": loss})
            report(f"resize to {new_ranks}", **log[-1])
        for h in handles:
            h.wait(timeout=300)
        tr.finalize()
        free(tr)
    report("resize", **obs.summary())
    return {"resizes": log}


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r}); this script runs on the chip only",
              file=sys.stderr)
        return 2
    use_compile_cache()      # before the first compile
    devs = jax.devices()
    check(resolve_impl() == "pallas", f"kernels resolve to {resolve_impl()}")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devs)} devices")
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")

    # four ranks split the batch: one sequence a chip
    cfg, shape = chip_config(4 if args.chips == 4 else GLOBAL_BATCH)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_resize(cfg, shape)
    else:
        ref = phase_reference(cfg, shape)
        gc.collect()
        phase_raw(cfg, shape, ref)
        gc.collect()
        phase_q8_delta(cfg, shape, ref)
    stats = devs[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"(device 0, whole run)")
    print(f"host peak RSS {peak_rss()} bytes; "
          f"wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
