"""ElasticTrainer: paper Listing 1 driven over a JAX TrainState.

Control flow is exactly the paper's malleable-app skeleton:

    MPI_Init_adapt            -> MalleableApp.init_adapt
    icheck_init               -> ICheckClient.init
    icheck_add_adapt          -> add_adapt_metas (every TrainState leaf,
                                 its region read from shape and sharding
                                 with no D2H, + data-iterator state become
                                 iCheck regions)
    icheck_restart            -> restart()  (fresh start if no checkpoint)
    loop:
        MPI_Probe_adapt       -> probe_adapt
        [MPI_Comm_adapt_begin -> adapt_begin
         icheck_redistribute  -> redistribute_mesh per region
         MPI_Comm_adapt_commit-> adapt_commit]
        train_step
        icheck_commit         -> commit (non-blocking, async agents)
        icheck_probe_agents   -> probe_agents

A "rank" is a data-parallel slice of the device mesh.  On resize the
TrainState is *not* gathered: agents move only the slices each new part
needs (plan.mesh_moves), then the state is re-materialized under the new
mesh's shardings.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import (ICheckClient, ICheckCluster, MalleableApp,
                        snapshot_pytree)
from repro.core import events as icheck_events
from repro.core import plan as planlib
from repro.core.snapshot import leaf_names, region_metas, restore_pytree
from repro.data import SyntheticLMData
from repro.obs import trace_id_for
from repro.optim import AdamWConfig, warmup_cosine
from repro.sharding import get_rules, use_rules

from .state import TrainState, make_train_state
from .step import make_train_step

DATA_REGION = "data_state"


def default_make_mesh(ranks: int) -> Mesh:
    devs = jax.devices()
    if len(devs) >= ranks:
        devs = devs[:ranks]
    elif devs[0].platform != "cpu":
        # an accelerator job is never silently run on fewer chips than ranks
        raise ValueError(f"{ranks} ranks need {ranks} devices, "
                         f"{devs[0].platform} has {len(devs)}")
    # else: CPU test runs keep logical ranks over the host's devices
    return Mesh(np.asarray(devs).reshape(len(devs)), ("data",))


class ElasticTrainer:
    def __init__(self, cfg: ModelConfig, shape: ShapeConfig,
                 cluster: ICheckCluster, app_id: str = "train",
                 ranks: int = 1, seed: int = 0,
                 opt_cfg: Optional[AdamWConfig] = None,
                 commit_every: int = 10, probe_every: int = 100,
                 global_batch: Optional[int] = None,
                 make_mesh: Callable[[int], Mesh] = default_make_mesh,
                 codec: str = "raw", replication: int = 1,
                 total_steps: int = 1000, adaptive_interval: bool = False,
                 step_sim_s: float = 0.0, overlap_resize: bool = False,
                 impl: Optional[str] = None):
        # the trainer's own trace: its construction, restart and steps
        # (each checkpoint's snapshot joins that checkpoint's trace)
        tracer = self.tracer = cluster.controller.tracer
        self.trace_id = tracer.unique(f"{app_id}/trainer")
        self._track = f"trainer/{app_id}"
        with tracer.span("trainer_init", self.trace_id, self._track,
                         root=True):
            self.cfg = cfg
            # kernel path of the step and the snapshot encode (None: by backend)
            self.impl = impl
            self.shape = shape
            self.app = MalleableApp(app_id, cluster.rm, ranks)
            self.proc_type = self.app.init_adapt()
            self.client = ICheckClient(app_id, cluster.controller, ranks=ranks,
                                       codec=codec, replication=replication)
            self.make_mesh = make_mesh
            self.mesh = make_mesh(ranks)
            self.rules = get_rules(cfg.rules)
            self.commit_every = commit_every
            self.probe_every = probe_every
            self.global_batch = global_batch or shape.global_batch
            self.opt_cfg = opt_cfg or AdamWConfig()
            self.schedule = warmup_cosine(self.opt_cfg.lr, warmup=20,
                                          total=total_steps)
            self.data = SyntheticLMData(cfg, shape, seed=seed)
            self.metrics_log: list = []
            self.resizes = 0
            self._pending_commits: list = []
            # zero-stall resize: on a forewarned/probed resize, open overlap
            # windows per region and keep training while the base checkpoint
            # streams; the adapt window proper shrinks to the cutover
            self.overlap_resize = overlap_resize
            self._adapt_handles: Optional[Dict[str, object]] = None
            self._adapt_ctx: Optional[dict] = None
            self.steps_during_resize = 0
            # adaptive checkpoint pacing: when enabled, commits follow the
            # IntervalController's solved cadence (sim-time based, re-announced
            # via INTERVAL_CHANGED events) instead of the static commit_every
            # step count; step_sim_s is the simulated compute cost per training
            # step, which is what advances the cadence clock in tests/benchmarks
            self.adaptive_interval = adaptive_interval
            self.step_sim_s = float(step_sim_s)
            self._clock = cluster.controller.clock
            if adaptive_interval and self.step_sim_s <= 0 \
                    and self._clock.time_scale == 0:
                # nothing would ever advance the cadence clock between commits:
                # the trainer would silently never checkpoint
                raise ValueError(
                    "adaptive_interval=True needs step_sim_s > 0 (or a cluster "
                    "with time_scale > 0) so sim time advances between steps")
            self._last_commit_t = self._clock.now()
            self.interval_changes = 0
            # checkpoint-service telemetry: observe the controller's event bus
            # instead of polling its audit list (drain completions, forewarnings,
            # codec degradations all land here asynchronously)
            self.ckpt_events: list = []
            self._unsubscribe = cluster.controller.bus.subscribe(
                self._on_ckpt_event,
                events=(icheck_events.CKPT_IN_L1, icheck_events.CKPT_IN_L2,
                        icheck_events.DRAIN_FAILED, icheck_events.CODEC_DEGRADED,
                        icheck_events.RESIZE_FOREWARNED,
                        icheck_events.INTERVAL_CHANGED))

            with tracer.span("trainer_init/state", self.trace_id, self._track):
                key = jax.random.key(seed)
                self.state = make_train_state(cfg, key, self.opt_cfg)
                self._shard_state()
                est = int(sum(np.prod(leaf.shape) * leaf.dtype.itemsize
                              for leaf in jax.tree.leaves(self.state)))
                tracer.note(bytes=est)
            self._jit_step()

            # icheck_init + add_adapt + (maybe) restart -- paper lines 5..9
            self.client.init(ckpt_bytes_estimate=est)
            with tracer.span("trainer_init/register", self.trace_id,
                             self._track):
                self._register_regions()
        self.restarted = self.restart_if_available()

    def _on_ckpt_event(self, ev) -> None:
        self.ckpt_events.append(ev.as_record())
        if ev.name == icheck_events.INTERVAL_CHANGED \
                and ev.payload.get("app") == self.client.app_id:
            # the client already re-paced its own ckpt_interval_s; count the
            # announcement so runs can report how often the loop retuned us
            self.interval_changes += 1

    def _commit_due(self, step: int) -> bool:
        if self.adaptive_interval:
            return (self._clock.now() - self._last_commit_t
                    >= self.client.ckpt_interval_s)
        return self.commit_every > 0 and step % self.commit_every == 0

    # ----------------------------------------------------------------- setup
    def _batch_sharding(self):
        return NamedSharding(self.mesh, PartitionSpec("data"))

    def _shard_state(self):
        """(Re)commit the TrainState onto the current mesh (DP-replicated
        params; batch over "data")."""
        rep = NamedSharding(self.mesh, PartitionSpec())
        self.state = jax.tree.map(lambda x: jax.device_put(x, rep),
                                  self.state)

    def _jit_step(self):
        step_fn = make_train_step(self.cfg, self.opt_cfg, self.schedule,
                                  impl=self.impl)

        def run(state, batch):
            with use_rules(self.mesh, self.rules):
                return step_fn(state, batch)

        self._step = jax.jit(run, donate_argnums=0)

    def _register_regions(self):
        # read from shapes and shardings: no byte of the state crosses to
        # the host (on a resume the restore overwrites it next)
        metas = region_metas(self.state)
        self.tracer.note(bytes=sum(m.nbytes for m in metas.values()),
                         regions=len(metas), host_bytes=0)
        self.client.add_adapt_metas(metas)
        self.client.add_adapt(DATA_REGION, (2,), "int64",
                              num_parts=1)

    # ----------------------------------------------------------- checkpoints
    def commit(self, blocking: bool = False):
        """icheck_commit: async snapshot -> agents (paper line 26).

        With a q8 codec the snapshot quantizes on device (q8-delta: XOR
        against the catalog's previous codes) before the D2H copy, and
        ``commit_snapshot`` ships those frames as-is."""
        step = int(self.state.step)
        data_parts = {DATA_REGION: {0: self.data.state_array()}}
        if self.client.codec in ("q8", "q8-delta"):
            snap = snapshot_pytree(self.state, step=step,
                                   codec=self.client.codec,
                                   chain_lookup=self.client.delta_chain_lookup,
                                   impl=self.impl, tracer=self.tracer)
            h = self.client.commit_snapshot(snap, extra_parts=data_parts,
                                            blocking=blocking)
        else:
            snap = snapshot_pytree(self.state, step=step, tracer=self.tracer)
            self.client.add_adapt_snapshot(snap)   # refresh region boxes
            parts = {name: r.parts for name, r in snap.regions.items()}
            parts.update(data_parts)
            h = self.client.commit(step, parts, blocking=blocking,
                                   snapshot_trace=snap.trace)
        self._pending_commits.append(h)
        self._last_commit_t = self._clock.now()
        return h

    def restart_if_available(self) -> bool:
        """icheck_restart: newest complete checkpoint -> TrainState."""
        with self.tracer.span("restart", self.trace_id, self._track):
            found = self.client.restart()
            if found is None:
                return False
            meta, regions, level = found
            with self.tracer.span("restore/place",
                                  trace_id_for(self.client.app_id,
                                               meta.ckpt_id), self._track):
                data_parts = regions.pop(DATA_REGION)
                self.data.restore(data_parts[0])
                template = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    self.state)
                region_meta = {name: meta.regions[name] for name in regions}
                self.state = restore_pytree(template, regions, region_meta,
                                            tracer=self.tracer)
                self._shard_state()
        return True

    # ---------------------------------------------------------------- resize
    def _redistribute(self, new_ranks: int):
        """Agent-side slice redistribution onto the new mesh (paper SSIII-B).

        Requires a checkpoint: commit (blocking) first, then pull only the
        slices each new part needs from the agents.
        """
        self.commit(blocking=True)
        new_mesh = self.make_mesh(new_ranks)
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)
        names = leaf_names(self.state)
        flat, treedef = jax.tree_util.tree_flatten(template)
        rep = NamedSharding(new_mesh, PartitionSpec())
        new_leaves = []
        for name, leaf in zip(names, flat):
            boxes = planlib.mesh_part_bounds(leaf.shape, rep)
            parts = self.client.redistribute_mesh(name, boxes)
            full = np.zeros(leaf.shape, leaf.dtype)
            for idx, arr in parts.items():
                sl = tuple(slice(lo, hi) for lo, hi in boxes[idx])
                full[sl] = arr
            new_leaves.append(jax.device_put(full, rep))
        self.mesh = new_mesh
        self.state = jax.tree_util.tree_unflatten(treedef, new_leaves)

    def _begin_overlap_adapt(self, new_ranks: int) -> None:
        """Phase 1: commit a base checkpoint, then open one overlap window
        per TrainState leaf targeting the new mesh's boxes.  The RM's resize
        event stays pending (``adapt_begin`` re-probes it at cutover), so
        training continues on the old ranks while the streams run."""
        self.commit(blocking=True)
        new_mesh = self.make_mesh(new_ranks)
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)
        names = leaf_names(self.state)
        flat, treedef = jax.tree_util.tree_flatten(template)
        rep = NamedSharding(new_mesh, PartitionSpec())
        handles: Dict[str, object] = {}
        boxes_by_name: Dict[str, tuple] = {}
        for name, leaf in zip(names, flat):
            boxes = planlib.mesh_part_bounds(leaf.shape, rep)
            boxes_by_name[name] = boxes
            handles[name] = self.client.redistribute_mesh(name, boxes,
                                                          overlap=True)
        self._adapt_handles = handles
        self._adapt_ctx = {"new_ranks": new_ranks, "new_mesh": new_mesh,
                           "boxes": boxes_by_name, "treedef": treedef,
                           "names": names, "flat": flat}

    def _finish_overlap_adapt(self) -> None:
        """Phase 2: quiesce (one last delta commit — the only frames the
        cutover still has to replay), switch partitions, rebuild the
        TrainState on the new mesh from the caught-up parts."""
        ctx = self._adapt_ctx
        window = self.app.adapt_begin()
        self.commit(blocking=True)
        new_mesh = ctx["new_mesh"]
        rep = NamedSharding(new_mesh, PartitionSpec())
        new_leaves = []
        for name, leaf in zip(ctx["names"], ctx["flat"]):
            boxes = ctx["boxes"][name]
            parts = self._adapt_handles[name].cutover()
            full = np.zeros(leaf.shape, leaf.dtype)
            for idx, arr in parts.items():
                sl = tuple(slice(lo, hi) for lo, hi in boxes[idx])
                full[sl] = arr
            new_leaves.append(jax.device_put(full, rep))
        self.mesh = new_mesh
        self.state = jax.tree_util.tree_unflatten(ctx["treedef"], new_leaves)
        self.app.adapt_commit()
        self.client.ranks = window.new_ranks
        self._jit_step()
        self.resizes += 1
        self._adapt_handles = None
        self._adapt_ctx = None

    def maybe_adapt(self) -> bool:
        """MPI_Probe_adapt + adapt window (paper lines 17-23).

        With ``overlap_resize`` the window is two-phase: the first probe
        that sees a resize opens background streams and returns False (no
        adaptation yet — training continues); once every stream is ready
        the next call performs the bounded-stall cutover."""
        if self._adapt_handles is not None:
            if all(h.ready() for h in self._adapt_handles.values()):
                self._finish_overlap_adapt()
                return True
            return False
        ev = self.app.probe_adapt()
        if ev is None:
            return False
        if self.overlap_resize:
            self._begin_overlap_adapt(ev.new_ranks)
            return False
        window = self.app.adapt_begin()
        self._redistribute(window.new_ranks)
        self.app.adapt_commit()
        self.client.ranks = window.new_ranks
        self._jit_step()
        self.resizes += 1
        return True

    # ------------------------------------------------------------------ run
    def run(self, steps: int) -> Dict:
        t0 = time.monotonic()
        for _ in range(steps):
            self.maybe_adapt()
            with self.tracer.span("step", self.trace_id, self._track):
                batch = self.data.next_batch(self.global_batch)
                batch = {k: jax.device_put(v, self._batch_sharding())
                         for k, v in batch.items()}
                self.state, metrics = self._step(self.state, batch)
                step = int(self.state.step)
                entry = {"step": step, "loss": float(metrics["loss"])}
                if "moe_rows" in metrics:
                    # kept on the device: read by moe_rows(), not here
                    entry["moe_rows"] = metrics["moe_rows"]
                self.metrics_log.append(entry)
            if self._adapt_handles is not None:
                # work retained inside the adapt window — the whole point of
                # overlapping: a stop-the-world resize gets zero of these
                self.steps_during_resize += 1
            if self.step_sim_s > 0:
                self._clock.sleep(self.step_sim_s)
            if self._commit_due(step):
                self.commit()
            if self.probe_every and step % self.probe_every == 0:
                self.client.probe_agents()
        return {"steps": steps, "wall_s": time.monotonic() - t0,
                "final_loss": self.metrics_log[-1]["loss"],
                "resizes": self.resizes,
                "steps_during_resize": self.steps_during_resize,
                "interval_changes": self.interval_changes,
                "ckpt_interval_s": self.client.ckpt_interval_s}

    def moe_rows(self, last: int) -> Optional[np.ndarray]:
        """Rows each held expert computed in each MoE layer over the last
        ``last`` steps, (steps, layers, experts), read from the device in
        one call and recorded as the counter ``moe_rows`` on the trainer's
        trace; None for a model without MoE layers."""
        kept = [e["moe_rows"] for e in self.metrics_log[-last:]
                if "moe_rows" in e] if last > 0 else []
        if not kept:
            return None
        rows = np.stack(jax.device_get(kept)).astype(np.int64)
        self.tracer.record("moe_rows", self.trace_id, self._track,
                           steps=len(kept),
                           rows=rows.sum(axis=0).tolist())
        return rows

    def finalize(self):
        if self._adapt_handles is not None:
            # run ended mid-window: release the scratch without switching
            for h in self._adapt_handles.values():
                h.cancel()
            self._adapt_handles = None
            self._adapt_ctx = None
        try:
            # every handle, done or not: a save that already failed raises
            for h in self._pending_commits:
                h.wait(timeout=60)
        finally:
            self.client.finalize()
            self._unsubscribe()
