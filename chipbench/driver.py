"""One general driver for every traffic mix: the program under test is
``ElasticTrainer`` over an ``ICheckCluster``; a mix's data file says what
a cycle does (restart from L1, steps, a commit, a kill) and what set-up
warms, and the driver runs cycles back to back for the window.

Everything the program receives is made here from the seed: the weights
(one jitted call, on the device, handed to the trainer by leaf name) and
the token batches.  The plain reference later starts from the same seed
and never sees the program's arrays.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import resource
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from . import checks
from .spans import Spans

DATA_REGION = "data_state"


def _rss() -> int:
    """Resident set size of this process now, in bytes."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * resource.getpagesize()
    except OSError:
        return 0


def peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def note(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Save:
    step: int
    ckpt: int
    call: float           # commit() called
    back: float           # commit() returned
    done: Optional[float] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Resume:
    start: float          # new trainer's construction begins
    built: float          # construction returned
    first_loss: float     # first step's loss on the host


class Observer:
    """Wall time of each checkpoint's COMMIT_DONE, and of its arrival in
    L2 (the PFS)."""

    def __init__(self, bus):
        from repro.core import events as E

        self._done_name = E.COMMIT_DONE
        self.done: Dict[int, float] = {}
        self.in_l2: Dict[int, float] = {}
        self._unsub = bus.subscribe(
            self._on_event, events=(E.COMMIT_DONE, E.CKPT_IN_L2))

    def _on_event(self, ev) -> None:
        seen = self.done if ev.name == self._done_name else self.in_l2
        seen.setdefault(ev.payload["ckpt"], time.perf_counter())

    def close(self) -> None:
        self._unsub()


class Driver:
    """Set-up, window and read-back of one run of one cell."""

    def __init__(self, cfg: dict, traffic: dict, family, seed: int,
                 spans: Spans):
        self.cfg, self.traffic, self.fam = cfg, traffic, family
        self.seed = int(seed)
        self.spans = spans
        self.saves: List[Save] = []
        self.resumes: List[Resume] = []
        # device fingerprints, read once the window has closed: the live
        # state before and after each commit(), and each restored state
        # with its data cursor
        self.save_prints: list = []
        self.restored: list = []
        self.step_spans: list = []
        self.losses: List[float] = []
        self.failed = 0
        self.attempted = 0
        self.checks: Dict[str, float] = {}
        self.readings: Dict[str, object] = {}
        self.rss_per_cycle: List[int] = []
        self._jits: Dict[str, object] = {}
        self._ref_jits: Dict[object, object] = {}
        self.trainer = None
        self.cluster = None

    # ------------------------------------------------------- the program
    def program_config(self):
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig

        cfg = self.cfg
        mcfg = dataclasses.replace(
            get_config(cfg["program_arch"]),
            **{field: cfg[key] for field, key in
               self.fam.PROGRAM_FIELDS.items()})
        shape = ShapeConfig("bench", "train", cfg["seq_len"],
                            cfg["global_batch"])
        return mcfg, shape

    def opt_config(self):
        from repro.optim import AdamWConfig

        o = self.cfg["optimizer"]
        return AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                           weight_decay=o["weight_decay"],
                           grad_clip=o["grad_clip"])

    def state_bytes(self) -> int:
        return sum(4 * int(np.prod(s))
                   for s in self.fam.state_names(self.cfg).values())

    def make_cluster(self):
        from repro.core import ICheckCluster

        c = self.cfg["cluster"]
        return ICheckCluster(
            n_icheck_nodes=c["n_icheck_nodes"],
            node_memory=c["node_memory_states"] * self.state_bytes(),
            time_scale=c["time_scale"], keep_l1=c["keep_l1"],
            delta_keyframe_every=c["delta_keyframe_every"],
            adaptive_interval=c["adaptive_interval"])

    def new_trainer(self):
        """The trainer a job builds; on a cluster that holds a checkpoint
        of the job it restores from it while it is built."""
        from repro.train import ElasticTrainer

        mcfg, shape = self.program_config()
        o = self.cfg["optimizer"]
        return ElasticTrainer(
            mcfg, shape, self.cluster, app_id="job", ranks=1, seed=0,
            opt_cfg=self.opt_config(), commit_every=0, probe_every=0,
            codec=self.traffic["codec"],
            replication=self.cfg["cluster"]["replication"],
            total_steps=o["total_steps"])

    def named_state(self, state=None) -> Dict[str, object]:
        from repro.core.snapshot import leaf_names
        import jax

        state = self.trainer.state if state is None else state
        return dict(zip(leaf_names(state), jax.tree.leaves(state)))

    def inject(self, trainer) -> None:
        """Replace the trainer's own random state with the benchmark's,
        made from the seed in one jitted call on the device, and its data
        with the benchmark's batches."""
        import jax
        from repro.core.snapshot import leaf_names

        names = leaf_names(trainer.state)
        flat, treedef = jax.tree_util.tree_flatten(trainer.state)
        want = self.fam.state_names(self.cfg)
        have = {n: tuple(x.shape) for n, x in zip(names, flat)}
        if have != {n: tuple(s) for n, s in want.items()}:
            raise SystemExit(
                f"the program's state does not match the configuration: "
                f"{sorted(set(have.items()) ^ set(want.items()))[:6]}")
        shardings = {n: x.sharding for n, x in zip(names, flat)}
        for x in flat:
            if not x.is_deleted():
                x.delete()
        trainer.state = None
        if "init" not in self._jits:
            self._jits["init"] = jax.jit(
                lambda kd: self.fam.init_state(self.cfg, kd),
                out_shardings=shardings)
        made = self._jits["init"](self.fam.key_data(self.seed))
        trainer.state = jax.tree_util.tree_unflatten(
            treedef, [made[n] for n in names])
        trainer.data = self.fam.SeededTokens(self.cfg, self.seed)

    def kill(self, trainer) -> None:
        """The job dies: its device memory goes with it and its threads
        stop; nothing is finalized, so its checkpoints stay registered."""
        import jax

        for leaf in jax.tree.leaves(trainer.state):
            leaf.delete()
        trainer.state = None
        trainer._unsubscribe()
        trainer.client._commit_q.put(None)
        trainer.client._unsub_interval()

    # ------------------------------------------------------- readings
    def _grad_norms(self) -> Dict[str, float]:
        """The first gradient as AdamW took it, from its first moment
        after one step: mu_1 = (1 - b1) g."""
        import jax
        import jax.numpy as jnp

        b1 = self.cfg["optimizer"]["b1"]
        st = self.named_state()
        mu = {n[len("opt/mu/"):]: x for n, x in st.items()
              if n.startswith("opt/mu/")}
        if "gnorm" not in self._jits:
            self._jits["gnorm"] = jax.jit(lambda t: {
                k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1 - b1)
                for k, v in t.items()})
        out = self._jits["gnorm"](mu)
        return {"params/" + k: float(v) for k, v in out.items()}

    def _change_norms(self) -> Dict[str, float]:
        """|params now - params at step 0| per leaf, on the device."""
        import jax
        import jax.numpy as jnp

        st = self.named_state()
        params = {n: x for n, x in st.items() if n.startswith("params/")}
        if "change" not in self._jits:
            fam, cfg = self.fam, self.cfg
            self._jits["change"] = jax.jit(lambda p, kd: {
                k: jnp.sqrt(jnp.sum(jnp.square(v - p0)))
                for (k, v), p0 in zip(
                    sorted(p.items()),
                    [fam.init_params(cfg, kd)[k] for k in sorted(p)])})
        out = self._jits["change"](params, self.fam.key_data(self.seed))
        return {k: float(v) for k, v in out.items()}

    def _step(self, trainer) -> None:
        with self.spans.span("step") as sp:
            trainer.run(1)
        self.step_spans.append(sp)
        self.losses.append(trainer.metrics_log[-1]["loss"])

    def _commit(self, trainer, blocking: bool = False):
        before = checks.fingerprints_async(self.named_state(trainer.state))
        with self.spans.span("commit") as sp:
            h = trainer.commit(blocking=blocking)
        self.save_prints.append(
            (before, checks.fingerprints_async(self.named_state(
                trainer.state))))
        self.saves.append(Save(step=int(trainer.metrics_log[-1]["step"]),
                               ckpt=h.ckpt_id, call=sp.start, back=sp.end))
        return h

    # --------------------------------------------------------- set-up
    def setup(self) -> None:
        tr = self.traffic
        self.cluster = self.make_cluster()
        self.observer = Observer(self.cluster.bus)
        with self.spans.span("build"):
            self.trainer = self.new_trainer()
        self.inject(self.trainer)
        n = tr["verified_steps"]
        for i in range(n):
            self._step(self.trainer)
            if i == 0:
                self.readings["grad_norms"] = self._grad_norms()
        self.readings["change_norms"] = self._change_norms()
        self.readings["losses"] = list(self.losses)
        st = tr["setup"]
        self.handles = []
        if st.get("commit"):
            self._commit(self.trainer, blocking=True)
            self.committed_cursor = self.trainer.data.state_array().copy()
            self.committed = checks.read(self.save_prints[-1][1])
        if st.get("warm_delta_encode"):
            # compile the delta encode at every leaf's shape; nothing is
            # framed or committed
            import jax
            import jax.numpy as jnp
            from repro.kernels.ckpt_codec import quantize, quantize_delta

            impl = self.trainer.impl
            for x in jax.tree.leaves(self.trainer.state):
                if jnp.issubdtype(x.dtype, jnp.floating):
                    # the snapshot encodes each device shard's own array
                    # against the codes of the previous encode
                    part = x.addressable_shards[0].data
                    prev, _ = quantize(part, impl=impl)
                    jax.block_until_ready(
                        quantize_delta(part, prev, impl=impl))
        k = st.get("uninterrupted_steps", 0)
        self.uninterrupted = []
        for _ in range(k):
            self._step(self.trainer)
            self.uninterrupted.append(self.losses[-1])
        if tr["cycle"].get("restart"):
            self.kill(self.trainer)
            self.trainer = None
        # what every resume is held to, warm-up cycles included
        self.mismatch = {"leaves": 0, "losses": 0, "cursor": 0}
        for _ in range(st.get("warm_cycles", 0)):
            self.cycle()
        self.settle()
        note(f"host peak rss after set-up: {peak_rss()}")
        self.saves.clear()
        self.resumes.clear()
        self.step_spans.clear()
        self.attempted = 0

    def settle(self, timeout: float = 120.0) -> None:
        """Set-up's checkpoints reach L2 and their files the disk before
        the window opens, so that no write of set-up runs on into it."""
        t = time.perf_counter()
        want = {s.ckpt for s in self.saves}
        while not want <= set(self.observer.in_l2) \
                and time.perf_counter() - t < timeout:
            time.sleep(0.05)
        missing = sorted(want - set(self.observer.in_l2))
        if missing:
            note(f"set-up checkpoints {missing} not in L2 after {timeout} s")
        os.sync()
        note(f"settle: {time.perf_counter() - t:.2f} s")

    # --------------------------------------------------------- a cycle
    def cycle(self) -> None:
        c = self.traffic["cycle"]
        first_loss = None
        if c.get("restart"):
            t0 = time.perf_counter()
            with self.spans.span("resume"):
                tr = self.new_trainer()
            built = time.perf_counter()
            if not tr.restarted:
                raise SystemExit("the new trainer found no checkpoint")
            # dispatched, not read: compared once the window has closed
            cursor = tr.data.state_array().copy()
            self.restored.append(
                (checks.fingerprints_async(self.named_state(tr.state)),
                 cursor))
            tr.data = self.fam.SeededTokens(self.cfg, self.seed)
            tr.data.restore(cursor)
            self.trainer = tr
        for i in range(c["steps"]):
            self._step(self.trainer)
            self.attempted += 1
            if i == 0 and c.get("restart"):
                first_loss = time.perf_counter()
        if c.get("restart"):
            got = self.losses[-c["steps"]:]
            want = self.uninterrupted[:c["steps"]]
            self.mismatch["losses"] += sum(1 for a, b in zip(got, want) if a != b) \
                + abs(len(want) - len(got))
            self.resumes.append(Resume(start=t0, built=built,
                                       first_loss=first_loss))
            self.attempted += 1
        if c.get("commit"):
            self.handles.append(self._commit(self.trainer))
            self.attempted += 1
        if c.get("kill"):
            self.kill(self.trainer)
            self.trainer = None
        self.rss_per_cycle.append(_rss())

    # --------------------------------------------------------- window
    def window(self, seconds: float) -> None:
        self.w0 = time.perf_counter()
        while time.perf_counter() - self.w0 < seconds:
            self.cycle()
        self.w1 = time.perf_counter()
        self.window_steps = len(self.step_spans)

    def await_saves(self) -> None:
        """Saves still in flight are awaited outside the window, and count."""
        for h, s in zip(self.handles, self.saves):
            try:
                h.wait(timeout=600)
            except Exception as e:  # noqa: BLE001 - a failed save is counted
                s.error = repr(e)
                self.failed += 1
        for s in self.saves:
            s.done = self.observer.done.get(s.ckpt)
            if s.done is None and s.error is None:
                s.error = "no commit_done event"
                self.failed += 1

    # ------------------------------------------------------ read-back
    def read_back(self) -> None:
        """The newest checkpoint, fetched through the client's restart and
        placed by ``restore_pytree`` one leaf at a time, against the live
        state it was taken from."""
        import jax
        from repro.core.snapshot import restore_pytree

        t = self.trainer
        found = t.client.restart()
        if found is None:
            raise SystemExit("no checkpoint to read back")
        meta, regions, level = found
        live = self.named_state()
        cursor = regions.pop(DATA_REGION)[0]
        ratio, ints_differ = 0.0, 0
        for name, x in live.items():
            template = _nest(name, jax.ShapeDtypeStruct(x.shape, x.dtype))
            got = restore_pytree(template, {name: regions.pop(name)},
                                 {name: meta.regions[name]})
            y = jax.tree.leaves(got)[0]
            if np.issubdtype(x.dtype, np.floating) \
                    and self.traffic["codec"].startswith("q8"):
                ratio = max(ratio, checks.half_scale_excess(x, y))
            else:
                ints_differ += checks.count_differing(
                    checks.fingerprints({name: x}),
                    checks.fingerprints({name: y}))
            y.delete()
        self.checks["restored_step_gap"] = float(
            abs(meta.step - int(self.trainer.state.step)))
        if self.traffic["codec"].startswith("q8"):
            self.checks["half_scale_ratio"] = ratio
        self.checks["leaves_differing"] = float(ints_differ)
        self.checks["cursor_differing"] = float(
            not np.array_equal(np.asarray(cursor).reshape(-1),
                               t.data.state_array()))
        note(f"read back ckpt {meta.ckpt_id} (step {meta.step}) from {level}")

    def save_checks(self) -> None:
        """Leaves of the live state that a commit() changed, over every
        save of the run."""
        self.checks["state_changed_by_save"] = float(sum(
            checks.count_differing(checks.read(a), checks.read(b))
            for a, b in self.save_prints))

    def restore_checks(self) -> None:
        """Every restored state (warm-up cycles included) against the
        committed one, leaf by leaf, with its data cursor."""
        m = self.mismatch
        for prints, cursor in self.restored:
            m["leaves"] = max(m["leaves"], checks.count_differing(
                checks.read(prints), self.committed))
            m["cursor"] = max(m["cursor"], int(
                not np.array_equal(cursor, self.committed_cursor)))
        self.checks["leaves_differing"] = float(m["leaves"])
        self.checks["losses_differing"] = float(m["losses"])
        self.checks["cursor_differing"] = float(m["cursor"])

    def release(self) -> None:
        """Free the program's device state and stop its threads."""
        import jax

        if self.trainer is not None:
            t = self.trainer
            for leaf in jax.tree.leaves(t.state):
                leaf.delete()
            t.state = None
            t.finalize()
            self.trainer = None
        self.observer.close()
        self.cluster.close()
        self.cluster = None
        self._jits.clear()
        gc.collect()
        jax.clear_caches()

    # ------------------------------------------------------ reference
    def reference(self, dtype=None, rows=None) -> dict:
        """Three steps of the plain reference from the seed."""
        import jax
        import jax.numpy as jnp

        fam, cfg = self.fam, self.cfg
        live = sum(x.nbytes for x in jax.live_arrays())
        if live > (1 << 30):
            note(f"reference starts with {live} bytes live on the device")
        jits = self._ref_jits
        if "init" not in jits:
            jits["init"] = jax.jit(lambda k: fam.init_state(cfg, k))
            jits["change"] = jax.jit(lambda p, k: {
                name: jnp.sqrt(jnp.sum(jnp.square(
                    v - fam.init_params(cfg, k)[name])))
                for name, v in p.items()})
        if (dtype, rows) not in jits:
            jits[(dtype, rows)] = fam.make_step(cfg, cfg["optimizer"],
                                                dtype=dtype, rows=rows)
        step = jits[(dtype, rows)]
        n = self.traffic["verified_steps"]
        kd = fam.key_data(self.seed)
        state = jits["init"](kd)
        data = fam.SeededTokens(cfg, self.seed)
        losses, gnorms = [], None
        for i in range(n):
            toks = jnp.asarray(data.next_batch(cfg["global_batch"])["tokens"])
            state, val, norms = step(state, toks)
            losses.append(float(val))
            if i == 0:
                gnorms = {k: float(v) for k, v in norms.items()}
        params = {k: v for k, v in state.items() if k.startswith("params/")}
        change = {k: float(v) for k, v in jits["change"](params, kd).items()}
        for x in jax.tree.leaves(state):
            x.delete()
        return {"losses": losses, "grad_norms": gnorms, "change_norms": change}

    def compare(self, ref: dict) -> Dict[str, float]:
        """The training numbers of the program against a reference."""
        r = self.readings
        moving = checks.moving_leaves(ref["grad_norms"])
        still = sorted(set(ref["grad_norms"]) - set(moving))
        if still:
            note(f"left out of change_gap (gradient nought): {still}")
        return {
            "loss_gap": checks.loss_gap(r["losses"], ref["losses"]),
            "grad_gap": checks.norm_gap(r["grad_norms"], ref["grad_norms"]),
            "change_gap": checks.norm_gap(r["change_norms"],
                                          ref["change_norms"], moving),
        }


def _nest(name: str, leaf):
    out = leaf
    for part in reversed(name.split("/")):
        out = {part: out}
    return out


def stderr(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
