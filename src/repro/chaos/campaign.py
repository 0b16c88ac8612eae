"""One chaos campaign: a seeded schedule fired into a live two-app cluster.

The workload is fixed so a seed's outcome is a function of its schedule
alone: app ``alpha`` (raw codec, 4 ranks) runs the benchmark harness's
failure-injected compute/checkpoint loop (``benchmarks.common
.run_ckpt_workload``) on a worker thread; app ``beta`` (q8-delta codec,
6 ranks, churning data) is stepped by the campaign's main loop and — when
the schedule says so — opens a zero-stall overlap resize window and cuts
over mid-chaos.  The :class:`ChaosInjector` polls sim time from both
drivers and fires each :class:`~repro.chaos.schedule.ChaosAction` the
first tick at or past its offset, clearing transient faults when their
``duration_s`` elapses.

Everything the invariants judge is collected into
:class:`CampaignEvidence` *while the cluster is still alive* (the leak
check scans live tiers/agents), then ``run_checks`` renders the verdict
and :func:`run_campaign` returns a deterministic report dict.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time
import traceback
from concurrent.futures import TimeoutError as _FutureTimeout
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import events as E
from repro.core.cluster import ICheckCluster
from repro.core.client import ICheckClient
from repro.core.services.journal import StaleEpochError
from repro.core.types import ICheckError, ShardKey
from repro.kernels.ckpt_codec.blocks import (dequantize_np, quantize_np,
                                             to_blocks_np)

from .invariants import run_checks
from .schedule import (CRASH_MODES, MID_WINDOW_FAULTS, ChaosAction,
                       ChaosSchedule, generate_schedule)

# the benchmark harness lives at the repo root, outside ``src`` — the
# campaign reuses its workload loop rather than forking a copy
_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
if _REPO_ROOT not in sys.path:  # pragma: no cover - import plumbing
    sys.path.insert(0, _REPO_ROOT)
from benchmarks.common import block_parts, run_ckpt_workload  # noqa: E402

# errors a fault is *allowed* to surface to a driver (the campaign records
# and tolerates them; anything else is a bug in the system under test).
# concurrent.futures.TimeoutError is a distinct type until Python 3.11.
TOLERATED_ERRORS = (
    ICheckError,
    ConnectionError,
    TimeoutError,
    _FutureTimeout,
    KeyError,
)

WALL_BUDGET_S = 120.0       # whole-campaign wall budget (stall backstop)
CUTOVER_WAIT_S = 30.0       # bounded wait on the overlap cutover handle
ALPHA_JOIN_S = 60.0         # bounded join on the workload thread
SIM_BOUND_FACTOR = 8.0      # sim-time bound = factor * horizon + 10s
CRASH_GRACE_S = 0.6         # sim grace for drain/window crash deferral
STALE_PROBE_WAIT_S = 5.0    # wall bound on one stale-epoch probe op


@dataclasses.dataclass
class CampaignEvidence:
    """Everything the invariant registry consumes, collected live."""

    cluster: ICheckCluster
    apps: Tuple[str, ...]
    records: List[dict]
    telemetry_snapshot: dict
    restore_checks: List[dict]
    restartable_obs: Dict[str, List[Tuple[int, Optional[int]]]]
    commit_counts: Dict[str, int]
    stalls: List[str]
    driver_errors: List[str]
    notes: List[str]
    resizes: int
    final_sim_t: float
    sim_bound_s: float
    # one entry per fired controller_crash action (see the crash hook in
    # run_campaign): journaled truth / PFS high-water captured just before
    # the crash, what recovery rebuilt, and the stale-epoch probe verdict
    recovery_reports: List[dict] = dataclasses.field(default_factory=list)


def _q8_roundtrip(x: np.ndarray) -> np.ndarray:
    """The numpy oracle for q8/q8-delta restores: a restore of commit *t*
    must equal this independent blockwise-q8 roundtrip of x_t — delta
    replay reconstructs the head's exact codes, so chain shape never
    enters the oracle."""
    flat = np.ascontiguousarray(x).reshape(-1)
    blocks, n = to_blocks_np(flat)
    codes, scales = quantize_np(blocks)
    return dequantize_np(codes, scales, n, x.dtype).reshape(x.shape)


class ChaosInjector:
    """Resolves a schedule's symbolic targets against the live cluster and
    fires/clears actions as sim time passes the offsets."""

    def __init__(self, cluster: ICheckCluster, schedule: ChaosSchedule,
                 apps: Tuple[str, ...], t0: float):
        self.cluster = cluster
        self.ctl = cluster.controller
        self.fault = cluster.fault
        self.apps = apps
        self.t0 = t0
        # topology snapshot at campaign start: symbolic node index i always
        # means the i-th *initial* node, dead or alive
        self.node_ids = [m.node_id for m in self.ctl.managers()]
        self._pending = sorted(schedule.actions, key=lambda a: a.at_s)
        self._clears: List[Tuple[float, str, object]] = []
        self._lock = threading.Lock()
        self.fired: List[str] = []
        # wired by run_campaign after the drivers exist: crash_hook(mode)
        # performs crash+recover+probes and returns a detail string;
        # crash_ready(mode) gates the "drain"/"window" timing modes
        self.crash_hook = None
        self.crash_ready = None

    # ------------------------------------------------------------- polling
    def poll(self, now: float) -> None:
        rel = now - self.t0
        with self._lock:
            due = [a for a in self._pending if a.at_s <= rel]
            self._pending = [a for a in self._pending if a.at_s > rel]
            clears = [c for c in self._clears if c[0] <= rel]
            self._clears = [c for c in self._clears if c[0] > rel]
        for action in due:
            self._fire(action, rel)
        for _, desc, fn in clears:
            try:
                fn()
            except TOLERATED_ERRORS:
                pass
            self.ctl.bus.publish(E.CHAOS_CLEARED, kind=desc)

    def quiesce(self) -> None:
        """Clear every outstanding transient; drop unfired actions."""
        with self._lock:
            clears, self._clears = self._clears, []
            self._pending = []
        for _, desc, fn in clears:
            try:
                fn()
            except TOLERATED_ERRORS:
                pass
            self.ctl.bus.publish(E.CHAOS_CLEARED, kind=desc)

    # ------------------------------------------------------------ dispatch
    def _mgr(self, node_id: str):
        for m in self.ctl.managers():
            if m.node_id == node_id:
                return m
        return None

    def _agent_for(self, target: Dict[str, int]):
        app = self.apps[int(target.get("app", 0)) % len(self.apps)]
        agents = self.ctl.agents_for(app)
        if not agents:
            return None
        return agents[int(target.get("agent_slot", 0)) % len(agents)]

    def _fire(self, action, rel: float) -> None:
        kind = action.kind
        params = dict(action.params)
        if kind == "mid_window_fault":
            kind = MID_WINDOW_FAULTS[int(params.pop("sub", 0))]
        duration = float(params.get("duration_s", 0.0))
        detail = "skipped (target gone)"
        if kind == "agent_death":
            agent = self._agent_for(action.target)
            if agent is not None:
                self.fault.kill_agent(agent.agent_id)
                detail = agent.agent_id
        elif kind == "multi_agent_death":
            # kill `count` agents of one app in the same tick — spanning
            # distinct nodes first, so several failure domains lose their
            # fragment of the same erasure stripe *simultaneously*
            app = self.apps[int(action.target.get("app", 0)) % len(self.apps)]
            agents = self.ctl.agents_for(app)
            count = max(2, int(params.get("count", 2)))
            victims, seen_nodes = [], set()
            for a in agents:                       # one per node first
                if a.node_id not in seen_nodes:
                    victims.append(a)
                    seen_nodes.add(a.node_id)
            for a in agents:                       # then fill up
                if a not in victims:
                    victims.append(a)
            victims = victims[:count]
            for a in victims:
                self.fault.kill_agent(a.agent_id)
            if victims:
                detail = ",".join(a.agent_id for a in victims)
        elif kind == "node_loss":
            node_id = self.node_ids[int(action.target.get("node", 0))
                                    % len(self.node_ids)]
            if not self.fault.node_dead(node_id):
                self.fault.kill_node(node_id)
                detail = node_id
        elif kind in ("nic_degrade", "nic_down"):
            node_id = self.node_ids[int(action.target.get("node", 0))
                                    % len(self.node_ids)]
            mgr = self._mgr(node_id)
            if mgr is not None and not self.fault.node_dead(node_id):
                nic = mgr.nic
                if kind == "nic_degrade":
                    nic.set_slowdown(float(params.get("slowdown", 8.0)))
                    undo = lambda: nic.set_slowdown(1.0)  # noqa: E731
                else:
                    nic.set_down(True)
                    # a node that died while its NIC was down stays severed
                    undo = lambda: (not self.fault.node_dead(node_id)  # noqa: E731
                                    and nic.set_down(False))
                self._push_clear(rel + duration, kind, undo)
                detail = node_id
        elif kind == "straggler":
            agent = self._agent_for(action.target)
            if agent is not None:
                aid = agent.agent_id
                self.fault.make_straggler(
                    aid, float(params.get("slowdown", 4.0)))
                self._push_clear(rel + duration, kind,
                                 lambda: self.fault.clear_straggler(aid))
                detail = aid
        elif kind == "partition":
            a = self.node_ids[int(action.target.get("node", 0))
                              % len(self.node_ids)]
            b = self.node_ids[int(action.target.get("peer", 1))
                              % len(self.node_ids)]
            if a != b:
                self.fault.partition_nodes(a, b)
                self._push_clear(rel + duration, kind,
                                 lambda: self.fault.heal_partition(a, b))
                detail = f"{a}|{b}"
        elif kind == "l3_outage":
            l3 = self.cluster.l3
            if l3 is not None:
                l3.set_outage(True)
                self._push_clear(rel + duration, kind,
                                 lambda: l3.set_outage(False))
                detail = "l3"
        elif kind == "controller_crash":
            mode = CRASH_MODES[int(params.pop("mode", 0)) % len(CRASH_MODES)]
            if self.crash_hook is None:
                detail = "skipped (no crash hook)"
            else:
                ready = self.crash_ready is None or self.crash_ready(mode)
                deadline = action.params.get("_deadline")
                if not ready and (deadline is None or rel < deadline):
                    # condition ("drain" inflight / "window" open) not met
                    # yet: requeue a little later, bounded by a sim grace
                    # after which the crash fires plain anyway
                    new_params = dict(action.params)
                    new_params.setdefault("_deadline", rel + CRASH_GRACE_S)
                    with self._lock:
                        self._pending.append(dataclasses.replace(
                            action, at_s=rel + 0.03, params=new_params))
                    return
                if not ready:
                    mode = f"{mode}->plain"   # grace expired, fire anyway
                detail = self.crash_hook(mode.split("->")[0])
                detail = f"{mode}:{detail}"
        self.fired.append(f"{kind}@{rel:.3f}:{detail}")
        self.ctl.bus.publish(E.CHAOS_INJECTED, kind=kind, at_s=rel,
                             detail=detail)

    def _push_clear(self, at_rel: float, desc: str, fn) -> None:
        with self._lock:
            self._clears.append((at_rel, desc, fn))


class _Oracle:
    """Per-app restore oracle: committed content, keyed by commit *step*.

    Step, not ckpt id: the step is chosen by the driver before the commit
    is attempted, so it stays meaningful even when the attempt's ack is
    severed mid-flight (a controller crash can land the agent writes and
    journal barrier, then kill the client's blocking wait — recovery
    legitimately serves that checkpoint, and the oracle must be able to
    judge its content).  Ckpt ids, by contrast, drift from steps the first
    time an attempt dies before the catalog allocates one."""

    def __init__(self, app: str, lossless: bool):
        self.app = app
        self.lossless = lossless
        self._by_step: Dict[int, Dict[str, Dict[int, np.ndarray]]] = {}

    def record(self, step: int,
               parts_by_region: Dict[str, Dict[int, np.ndarray]]) -> None:
        snap: Dict[str, Dict[int, np.ndarray]] = {}
        for region, parts in parts_by_region.items():
            snap[region] = {
                p: (np.copy(x) if self.lossless else _q8_roundtrip(x))
                for p, x in parts.items()}
        self._by_step[int(step)] = snap

    def verify(self, restored, out: List[dict]) -> None:
        """Append one restore-comparison record (consumed by the
        ``restore_bit_identity`` invariant)."""
        if restored is None:
            out.append({
                "app": self.app,
                "ckpt": -1,
                "ok": True,
                "detail": "nothing restartable (skipped)",
                "skipped": True,
            })
            return
        meta, parts_by_region, level = restored
        ckpt = int(meta.ckpt_id)
        want = self._by_step.get(int(meta.step))
        if want is None:
            out.append({
                "app": self.app,
                "ckpt": ckpt,
                "ok": False,
                "detail": f"restored ckpt {ckpt} (step {meta.step}) was "
                          f"never attempted by the harness",
            })
            return
        for region, parts in want.items():
            got_parts = parts_by_region.get(region, {})
            for p, ref in parts.items():
                got = got_parts.get(p)
                if got is None or got.shape != ref.shape or \
                        not np.array_equal(np.asarray(got), ref):
                    out.append({
                        "app": self.app,
                        "ckpt": ckpt,
                        "ok": False,
                        "detail": f"{region}[{p}] mismatch vs oracle "
                                  f"(level={level})",
                    })
                    return
        out.append({
            "app": self.app,
            "ckpt": ckpt,
            "ok": True,
            "detail": f"bit-identical (level={level})",
        })


class _BetaDriver:
    """Main-loop-stepped q8-delta app with churn and the overlap resize."""

    def __init__(self, cluster: ICheckCluster, client: ICheckClient,
                 schedule: ChaosSchedule, seed: int, horizon_s: float,
                 oracle: _Oracle, ev_sink: dict, self_test: bool,
                 crash_self_test: bool = False):
        self.cluster = cluster
        self.client = client
        self.schedule = schedule
        self.horizon_s = horizon_s
        self.oracle = oracle
        self.sink = ev_sink
        self.self_test = self_test
        self.crash_self_test = crash_self_test
        self._self_test_done = False
        self.rng = np.random.default_rng(seed + 7919)
        self.x = self.rng.normal(size=6144).astype(np.float32)
        self.num_parts = client.ranks
        self.parts = block_parts(self.x, self.num_parts)
        self.step = 0
        self.work_done = 0.0
        self.last_commit_t: Optional[float] = None
        self.interval_s = 0.30
        self.slice_s = 0.02
        self.handle = None          # open ResizeCutoverHandle
        self.resize_done = schedule.resize_at_s is None
        self.done = False

    # ----------------------------------------------------------- stepping
    def tick(self, now: float, t0: float) -> None:
        if self.done:
            return
        rel = now - t0
        clock = self.cluster.clock
        self._maybe_resize(rel)
        if self.last_commit_t is None or \
                now - self.last_commit_t >= self.interval_s:
            self._commit()
            self.last_commit_t = clock.now()
            return
        dt = min(self.slice_s, self.horizon_s - self.work_done)
        clock.sleep(dt)
        self.work_done += dt
        if self.work_done >= self.horizon_s and self.resize_done \
                and self.handle is None:
            self.done = True

    def _churn(self) -> None:
        # sparse churn: mutate ~1/16 of the field so q8 deltas stay sparse
        # but never empty
        idx = self.rng.integers(0, self.x.size, size=self.x.size // 16)
        self.x[idx] += self.rng.normal(scale=0.1,
                                       size=idx.size).astype(np.float32)
        self.parts = block_parts(self.x, self.num_parts)

    def _commit(self) -> None:
        self._churn()
        drain = self.step % 2 == 0   # exercise L2 drains + L3 trickle
        # record before the attempt: if a fault severs the *ack* after the
        # agent writes land, the checkpoint is still durable and the
        # oracle must be able to judge a restore of it by content
        self.oracle.record(self.step, {"field": self.parts})
        try:
            self.client.commit(self.step, {"field": self.parts},
                               blocking=True, drain=drain)
            self.sink["commit_counts"]["beta"] += 1
        except TOLERATED_ERRORS as exc:
            self.sink["notes"].append(
                f"beta commit {self.step} failed under fault: "
                f"{type(exc).__name__}")
        self.step += 1
        if self.self_test and not self._self_test_done and \
                self.sink["commit_counts"]["beta"] >= 2:
            self._suppress_chain_reset()
        if self.crash_self_test and not self._self_test_done and \
                self.sink["commit_counts"]["beta"] >= 2:
            self._suppress_journal()

    def _suppress_chain_reset(self) -> None:
        """Self-test fault: detach the catalog's mandatory chain-reset
        subscriber, then fire a rank failure while a delta chain is live —
        the ``delta_chain_reset_policy`` check must go CRIT."""
        self._self_test_done = True
        ctl = self.cluster.controller
        ctl.catalog._unsub_chain()
        ctl.bus.publish(E.APP_RANK_FAILED, app=self.client.app_id, rank=0)
        self.sink["notes"].append("self-test: chain-reset subscriber "
                                  "suppressed + rank failure injected")

    def _suppress_journal(self) -> None:
        """Crash self-test fault: silently stop journaling, keep committing
        and draining, then let the scheduled controller crash fire — the
        recovery must come up knowing less than the PFS holds, and the
        ``recovery_fidelity`` check must go CRIT."""
        self._self_test_done = True
        j = self.cluster.controller.journal
        if j is not None:
            j.enabled = False
        self.sink["notes"].append("self-test: journal writes suppressed "
                                  "ahead of the controller crash")

    # ------------------------------------------------------------- resize
    def _maybe_resize(self, rel: float) -> None:
        sc = self.schedule
        if self.resize_done and self.handle is None:
            return
        if self.handle is None and rel >= sc.resize_at_s:
            try:
                self.handle = self.client.redistribute(
                    "field", sc.resize_new_parts, via="peer", overlap=True)
            except TOLERATED_ERRORS as exc:
                self.sink["notes"].append(
                    f"overlap open failed: {type(exc).__name__}")
                self.resize_done = True
            return
        if self.handle is not None and \
                rel >= sc.resize_at_s + sc.resize_window_s:
            self._cutover()

    def _cutover(self) -> None:
        handle, self.handle = self.handle, None
        self.resize_done = True
        if not handle.wait(timeout=CUTOVER_WAIT_S):
            self.sink["stalls"].append(
                f"cutover handle not ready within {CUTOVER_WAIT_S:.0f}s "
                f"wall (wedged overlap window)")
            handle.cancel()
            return
        try:
            new_parts = handle.cutover()
        except TOLERATED_ERRORS as exc:
            self.sink["notes"].append(
                f"cutover degraded: {type(exc).__name__}")
            handle.cancel()
            return
        self.num_parts = self.schedule.resize_new_parts
        self.client.commit_redistribution("field", self.num_parts)
        self.x = np.concatenate(
            [np.asarray(new_parts[p]).reshape(-1)
             for p in sorted(new_parts)]).astype(np.float32)
        self.parts = dict(new_parts)
        self.sink["resizes"] += 1

    def abort(self) -> None:
        if self.handle is not None:
            self.handle.cancel()
            self.handle = None


def run_campaign(seed: int, schedule: Optional[ChaosSchedule] = None,
                 self_test: bool = False, controller_crash: bool = False,
                 crash_self_test: bool = False) -> dict:
    """Run one campaign; returns the deterministic JSON-able report.

    ``controller_crash=True`` draws one controller crash into the seed's
    schedule (crash -> journal replay -> reconciliation -> epoch fencing,
    judged by the ``recovery_fidelity`` invariant).  ``crash_self_test``
    suppresses journal writes mid-campaign and schedules a crash — the
    fidelity check must then go CRIT (a green run is a runner failure).
    """
    if schedule is None:
        if crash_self_test:
            # a quiet campaign plus one late plain crash: the only signal
            # competing for the verdict is the suppressed journal itself.
            # _deadline far past the horizon disables the plain-mode
            # fallback — the crash defers until the violation is armed
            # (suppression fired + one unjournaled commit landed)
            schedule = ChaosSchedule(
                seed=seed, horizon_s=2.4, actions=(
                    ChaosAction(at_s=1.7, kind="controller_crash",
                                params={"mode": 0.0, "_deadline": 1e9}),))
        elif self_test:
            # the deliberate violation needs a quiet campaign: no scheduled
            # faults competing with the suppressed reset for the verdict
            schedule = ChaosSchedule(seed=seed, horizon_s=2.4, actions=())
        else:
            schedule = generate_schedule(seed,
                                         controller_crash=controller_crash)
    horizon = schedule.horizon_s
    apps = ("alpha", "beta")
    # trace=True: spans read the clocks but never advance the sim clock or
    # steer control flow, so tracing leaves the schedule deterministic —
    # and a red seed's flight dump then carries the span tree of the
    # failure window (sim_t beside each wall time), not just raw events
    cluster = ICheckCluster(n_icheck_nodes=3, n_spare_nodes=2,
                            adaptive_interval=False, l3=True,
                            keep_l1=3, keep_l2=2, keep_l3=4,
                            delta_keyframe_every=4, trace=True)
    sink = {
        "commit_counts": {"alpha": 0, "beta": 0},
        "notes": [],
        "stalls": [],
        "resizes": 0,
    }
    restore_checks: List[dict] = []
    driver_errors: List[str] = []
    recovery_reports: List[dict] = []
    obs: Dict[str, List[Tuple[int, Optional[int]]]] = {a: [] for a in apps}
    try:
        ctl = cluster.controller
        rng_a = np.random.default_rng(seed + 101)
        arr_a = rng_a.normal(size=4096).astype(np.float32)
        # alpha runs erasure-coded L1 durability (k=4, m=1): every commit
        # scatters 4 data + 1 parity fragments across failure domains, so
        # the multi_agent_death action class and the node losses exercise
        # the peer-rebuild path instead of whole-shard re-replication
        alpha = ICheckClient("alpha", ctl, ranks=4, codec="raw",
                             durability="ec", ec_k=4, ec_m=1).init(
                                 ckpt_bytes_estimate=arr_a.nbytes)
        alpha.add_adapt("state", arr_a.shape, "float32")
        alpha_parts = block_parts(arr_a, 4)
        beta = ICheckClient("beta", ctl, ranks=6, codec="q8-delta",
                            keyframe_every=4).init(ckpt_bytes_estimate=0)
        beta.add_adapt("field", (6144,), "float32")

        oracle_a = _Oracle("alpha", lossless=True)
        oracle_b = _Oracle("beta", lossless=False)
        t0 = cluster.clock.now()
        injector = ChaosInjector(cluster, schedule, apps, t0)
        beta_drv = _BetaDriver(cluster, beta, schedule, seed, horizon,
                               oracle_b, sink, self_test,
                               crash_self_test=crash_self_test)

        def crash_ready(mode: str) -> bool:
            if crash_self_test:
                # the self-test crash defers until the violation is armed:
                # journal suppressed *and* one unjournaled commit has been
                # acknowledged (else a slow start could crash before the
                # journal and catalog ever diverge, and the run reads green)
                return (beta_drv._self_test_done
                        and sink["commit_counts"]["beta"] >= 3)
            if mode == "drain":
                return ctl.drains.stats()["active"] > 0
            if mode == "window":
                return beta_drv.handle is not None
            return True

        def do_controller_crash(mode: str) -> str:
            """The tentpole's end-to-end sequence, fired mid-chaos: capture
            ground truth, hard-crash the control plane, warm-recover from
            the journal, then prove fencing and restorability."""
            j = ctl.journal
            truth_before = dict(j.truth()) if j is not None else {}
            pfs_before = {
                app: max(cluster.pfs.list_checkpoints(app), default=-1)
                for app in apps}
            # the journal-before-state barrier means every checkpoint id the
            # live catalog has issued was journaled *first* — so recovery's
            # max_known must cover the pre-crash catalog, deterministically,
            # no matter where the crash lands relative to drain timing
            known_before = {}
            with ctl._lock:
                for app in apps:
                    try:
                        ids = list(ctl.app(app).checkpoints)
                    except TOLERATED_ERRORS:
                        ids = []
                    known_before[app] = max(ids, default=-1)
            old_epoch = ctl.fence.current
            ctl.crash()
            report = ctl.recover()
            # stale-epoch probe: an op stamped with the pre-crash epoch
            # must be refused by the fence, not silently applied
            probe = "skipped"
            for agent in ctl.agents_for("alpha") + ctl.agents_for("beta"):
                try:
                    fut = agent.put(
                        ShardKey("alpha", 999_999, "_staleprobe", 0),
                        b"\x00" * 8, epoch=old_epoch)
                    fut.result(timeout=STALE_PROBE_WAIT_S)
                    probe = "accepted"      # fence failed — CRIT downstream
                    break
                except StaleEpochError:
                    probe = "rejected"
                    break
                except TOLERATED_ERRORS:
                    continue                # dead/stopped agent: try another
            # post-recovery restores, judged against the same numpy
            # oracles; a tolerated fault-window exception is *skipped*
            # here, not failed — other scheduled faults are still live at
            # this point, and the post-quiesce final sweep is the
            # authoritative judge of restorability
            post: List[dict] = []
            for client, oracle in ((alpha, oracle_a), (beta, oracle_b)):
                try:
                    oracle.verify(client.restart(), post)
                except TOLERATED_ERRORS as exc:
                    post.append({"app": client.app_id, "ckpt": -1,
                                 "ok": True, "skipped": True,
                                 "detail": f"post-recovery restore raised "
                                           f"{type(exc).__name__} under "
                                           f"live faults (skipped)"})
            restore_checks.extend(post)
            post_latest: Dict[str, Optional[int]] = {}
            for app in apps:
                try:
                    got = ctl.latest_restartable(app)
                except TOLERATED_ERRORS:
                    got = None
                post_latest[app] = None if got is None \
                    else int(got[0].ckpt_id)
            # the live workloads keep committing *during* the recovery
            # sequence, so the "never newer than journaled truth" bound is
            # the journal as of after the post_latest measurement — truth
            # only grows, and anything restartable at measurement time was
            # journaled (barrier write) before it committed
            truth_after = dict(j.truth()) if j is not None else {}
            recovery_reports.append({
                "mode": mode,
                "epoch": int(report["epoch"]),
                "truth_before": truth_before,
                "truth_after": truth_after,
                "pfs_before": pfs_before,
                "known_before": known_before,
                "max_known": {
                    a: int(report["apps"].get(a, {}).get("max_known", -1))
                    for a in apps},
                "post_latest": post_latest,
                "stale_probe": probe,
                "post_restores": post,
                "chains_reset": int(report["chains_reset"]),
                "downgraded": len(report["downgraded"]),
                "drains_resubmitted": int(report["drains_resubmitted"]),
            })
            return f"epoch={report['epoch']} probe={probe}"

        injector.crash_ready = crash_ready
        injector.crash_hook = do_controller_crash

        # alpha's rank-failure times: seeded, inside the active window
        frng = np.random.default_rng(seed + 0xA1FA)
        fail_times = [t0 + float(x) for x in
                      np.sort(frng.uniform(0.25, 0.95,
                                           size=int(frng.integers(1, 3))))
                      * horizon]

        def observe() -> None:
            for app in apps:
                got = ctl.latest_restartable(app)
                obs[app].append((len(ctl.events),
                                 None if got is None
                                 else int(got[0].ckpt_id)))

        def on_tick(now: float) -> None:
            injector.poll(now)

        def on_restart(restored) -> None:
            oracle_a.verify(restored, restore_checks)

        def alpha_main() -> None:
            oracle_a.record(0, {"state": alpha_parts})
            try:
                stats = run_ckpt_workload(
                    cluster, alpha, {"state": alpha_parts},
                    total_work_s=horizon, failure_times=fail_times,
                    interval_fn=lambda: 0.25, work_slice_s=0.02,
                    keep_l1=3, on_tick=on_tick, on_restart=on_restart)
                sink["commit_counts"]["alpha"] = int(stats["commits"])
            except TOLERATED_ERRORS as exc:
                sink["notes"].append(
                    f"alpha workload aborted under fault: "
                    f"{type(exc).__name__}")
            except Exception as exc:  # noqa: BLE001 - judged by no_stall
                driver_errors.append(
                    f"alpha: {exc!r}\n{traceback.format_exc()}")

        # alpha's oracle can't see individual commit steps (the workload
        # owns its commit loop) — but alpha never mutates its parts, so
        # every checkpoint has identical content and one record per step
        # suffices; pre-register a generous step range
        for ck in range(200):
            oracle_a.record(ck, {"state": alpha_parts})
        alpha_thread = threading.Thread(target=alpha_main, daemon=True,
                                        name="chaos-alpha")
        wall_start = time.monotonic()
        alpha_thread.start()

        pumped = 0.0
        while not (beta_drv.done and not alpha_thread.is_alive()):
            now = cluster.clock.now()
            injector.poll(now)
            beta_drv.tick(now, t0)
            observe()
            if beta_drv.done and alpha_thread.is_alive():
                # keep sim time flowing so pending fault *clears* fire while
                # alpha drains its tail — but bounded, or a wall-blocked
                # alpha would let this idle loop inflate sim time past the
                # no-stall bound
                if pumped < 2.0 * horizon:
                    cluster.clock.sleep(0.02)
                    pumped += 0.02
                time.sleep(0.002)
            if time.monotonic() - wall_start > WALL_BUDGET_S:
                sink["stalls"].append(
                    f"campaign exceeded {WALL_BUDGET_S:.0f}s wall budget")
                break
        alpha_thread.join(timeout=ALPHA_JOIN_S)
        if alpha_thread.is_alive():
            sink["stalls"].append(
                f"alpha workload thread still running after "
                f"{ALPHA_JOIN_S:.0f}s wall join")
        if crash_self_test and not recovery_reports:
            # the deferred self-test crash never found its arming window
            # inside the loop (e.g. a wall-budget bailout): fire it now —
            # by end of campaign the suppressed journal has provably
            # diverged from the catalog, so the verdict stays meaningful
            sink["notes"].append("self-test crash fired post-loop "
                                 "(in-loop deferral never armed)")
            do_controller_crash("plain")
        beta_drv.abort()

        # settle: clear transients, let the health loop finish processing
        injector.quiesce()
        time.sleep(0.4)
        cluster.clock.sleep(0.2)

        # final restore sweep: both apps, faults cleared
        for client, oracle in ((alpha, oracle_a), (beta, oracle_b)):
            try:
                oracle.verify(client.restart(), restore_checks)
            except TOLERATED_ERRORS as exc:
                restore_checks.append(
                    {"app": client.app_id, "ckpt": -1, "ok": False,
                     "detail": f"final restore raised "
                               f"{type(exc).__name__} after quiesce"})
        observe()

        snapshot = cluster.telemetry.snapshot()
        evidence = CampaignEvidence(
            cluster=cluster, apps=apps, records=list(ctl.events),
            telemetry_snapshot=snapshot, restore_checks=restore_checks,
            restartable_obs=obs,
            commit_counts=dict(sink["commit_counts"]),
            stalls=list(sink["stalls"]), driver_errors=driver_errors,
            notes=list(sink["notes"]), resizes=int(sink["resizes"]),
            final_sim_t=cluster.clock.now() - t0,
            sim_bound_s=SIM_BOUND_FACTOR * horizon + 10.0,
            recovery_reports=list(recovery_reports))
        results = run_checks(evidence)
        # any non-OK verdict dumps the flight recorder while the cluster is
        # still alive: the last N events + spans around the failure, keyed
        # by seed so one red seed produces exactly one dump
        flight_dump = None
        failing = [r.as_dict() for r in results if int(r.status) >= 1]
        if failing:
            suffix = "_selftest" if (self_test or crash_self_test) else ""
            flight_dump = ctl.flight.dump(
                f"chaos_seed_{seed}{suffix}",
                extra={"seed": int(seed), "failing_checks": failing})
        for client in (alpha, beta):
            try:
                client.finalize()
            except TOLERATED_ERRORS:
                pass
    finally:
        cluster.close()

    worst = max((r.status for r in results), default=0)
    return {
        "seed": int(seed),
        "self_test": bool(self_test or crash_self_test),
        "ok": int(worst) < 2,
        "worst": ["OK", "WARN", "CRIT"][int(worst)],
        "schedule": schedule.as_dict(),
        "checks": [r.as_dict() for r in results],
        "recovery_reports": recovery_reports,
        "flight_dump": flight_dump,
    }
