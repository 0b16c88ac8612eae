"""B5 -- multilevel restart latency (paper SSII): restoring from L1 (agent
memory over the fabric) vs L2 (parallel file system), plus the L1-replica
failover path (kill the primary replica's agent; restart must still come
from a surviving L1 copy).

``--adaptive`` (also B5A in the driver) runs the closed-loop interval
benchmark instead: the same failure-injected workload under a fixed
checkpoint interval vs the Young/Daly IntervalController driven by live
TelemetryService estimates, comparing wasted work + checkpoint overhead.
"""
from __future__ import annotations

import argparse
import os

import numpy as np

from repro.core import ICheckClient, ICheckCluster

from .common import (block_parts, failure_schedule, fmt_bytes,
                     run_ckpt_workload, save)

OBS_DIR = os.path.join(os.path.dirname(__file__), "..", "artifacts", "obs")

PAYLOAD = 128 << 20
PARTS = 16
PFS_BW = 10e9
NIC_BW = 25e9


def run(verbose: bool = True) -> dict:
    data = np.random.default_rng(0).standard_normal(
        PAYLOAD // 4).astype(np.float32)
    rows = {}
    with ICheckCluster(n_icheck_nodes=4, node_memory=8 << 30,
                       nic_bandwidth=NIC_BW, pfs_bandwidth=PFS_BW) as c:
        from .common import FixedCountPolicy

        c.controller.policy = FixedCountPolicy(4)  # spread the 2 replicas
        client = ICheckClient("app", c.controller, ranks=PARTS,
                              replication=2).init(
            ckpt_bytes_estimate=PAYLOAD)
        client.add_adapt("x", data.shape, "float32", num_parts=PARTS)
        client.commit(0, {"x": block_parts(data, PARTS)}, blocking=True)
        c.controller.wait_for_drains(timeout=60)

        # -- L1 restart
        t0 = c.clock.now()
        meta, parts, level = client.restart()
        rows["l1"] = {"sim_s": c.clock.now() - t0, "level": level}
        assert level == "l1"

        # -- L1 with primary-replica failure (failover to replica 1)
        primary = c.controller.agents_for("app")[0]
        c.fault.kill_agent(primary.agent_id)
        t0 = c.clock.now()
        meta, parts, level = client.restart()
        rows["l1_failover"] = {"sim_s": c.clock.now() - t0, "level": level}

        # -- L2 restart (all agents dead -> PFS)
        for mgr in c.controller.managers():
            for agent in list(mgr.agents()):
                c.fault.kill_agent(agent.agent_id)
        t0 = c.clock.now()
        meta, parts, level = client.restart()
        rows["l2"] = {"sim_s": c.clock.now() - t0, "level": level}
        assert level == "l2"
        got = np.concatenate([parts["x"][i] for i in range(PARTS)])
        np.testing.assert_array_equal(got, data)
        # the commit/drain numbers come straight from the TelemetryService
        # (the bus-fed metrics exporter) rather than ad-hoc audit scans
        telemetry = c.telemetry.snapshot()["per_app"]["app"]
        client.finalize()

    out = {"payload": PAYLOAD, "rows": rows,
           "l2_over_l1": rows["l2"]["sim_s"] / max(rows["l1"]["sim_s"], 1e-9),
           "telemetry": telemetry}
    save("b5_restart", out)
    if verbose:
        print(f"\nB5 restart latency ({fmt_bytes(PAYLOAD)}, repl=2):")
        for k, r in rows.items():
            print(f"  {k:12s}: {r['sim_s']:.3f}s sim (from {r['level']})")
        print(f"  L1 is {out['l2_over_l1']:.1f}x faster than PFS restart")
        print(f"  telemetry: commit {telemetry['commit_latency_s']:.3f}s sim "
              f"EWMA, drain {fmt_bytes(telemetry['drain_rate_Bps'])}/s EWMA")
    return out


# ---------------------------------------------------------------- adaptive
ADAPTIVE_PAYLOAD = 48 << 20
ADAPTIVE_PARTS = 8
ADAPTIVE_MTBF_S = 30.0
ADAPTIVE_WORK_S = 180.0
FIXED_INTERVAL_S = 12.0


def _interval_policy_run(adaptive: bool, data, failure_times,
                         total_work_s: float) -> dict:
    """One policy leg: identical cluster + failure schedule, only the
    interval source differs (static config vs IntervalController)."""
    with ICheckCluster(n_icheck_nodes=2, n_spare_nodes=0,
                       node_memory=2 << 30, nic_bandwidth=1e9,
                       adaptive_interval=adaptive,
                       default_mtbf_s=300.0) as c:
        client = ICheckClient("app", c.controller, ranks=ADAPTIVE_PARTS,
                              ckpt_interval_s=FIXED_INTERVAL_S).init(
            ckpt_bytes_estimate=data.nbytes)
        client.add_adapt("x", data.shape, "float32",
                         num_parts=ADAPTIVE_PARTS)
        parts = {"x": block_parts(data, ADAPTIVE_PARTS)}
        # adaptive: the client's ckpt_interval_s tracks INTERVAL_CHANGED
        # events; fixed: it stays at the registered constant
        res = run_ckpt_workload(c, client, parts, total_work_s,
                                failure_times,
                                interval_fn=lambda: client.ckpt_interval_s)
        snap = c.telemetry.snapshot()
        res["telemetry"] = snap["per_app"]["app"]
        res["mtbf_estimate_s"] = snap["per_app"]["app"]["mtbf_s"]
        res["commit_cost_estimate_s"] = \
            snap["per_app"]["app"]["commit_latency_s"]
        client.finalize()
    return res


def run_adaptive(verbose: bool = True, total_work_s: float = ADAPTIVE_WORK_S,
                 mtbf_s: float = ADAPTIVE_MTBF_S, seed: int = 0) -> dict:
    data = np.random.default_rng(1).standard_normal(
        ADAPTIVE_PAYLOAD // 4).astype(np.float32)
    failures = failure_schedule(mtbf_s, 4.0 * total_work_s, seed=seed)
    fixed = _interval_policy_run(False, data, failures, total_work_s)
    adaptive = _interval_policy_run(True, data, failures, total_work_s)
    out = {
        "payload": ADAPTIVE_PAYLOAD,
        "injected_mtbf_s": mtbf_s,
        "fixed_interval_s": FIXED_INTERVAL_S,
        "fixed": fixed,
        "adaptive": adaptive,
        "overhead_reduction": 1.0 - adaptive["total_overhead_s"]
        / max(fixed["total_overhead_s"], 1e-9),
    }
    save("b5a_adaptive_interval", out)
    if verbose:
        print(f"\nB5A adaptive checkpoint interval "
              f"({fmt_bytes(ADAPTIVE_PAYLOAD)} ckpt, injected MTBF "
              f"{mtbf_s:.0f}s, {total_work_s:.0f}s of work):")
        for name, r in (("fixed", fixed), ("adaptive", adaptive)):
            print(f"  {name:9s} interval={r['final_interval_s']:7.2f}s "
                  f"commits={r['commits']:4d} failures={r['failures']:2d} "
                  f"wasted={r['wasted_work_s']:7.2f}s "
                  f"ckpt={r['ckpt_overhead_s']:6.2f}s "
                  f"total_overhead={r['total_overhead_s']:7.2f}s")
        print(f"  telemetry estimates (adaptive leg): "
              f"C={adaptive['commit_cost_estimate_s']:.3f}s "
              f"MTBF={adaptive['mtbf_estimate_s']:.1f}s")
        print(f"  adaptive cuts total overhead by "
              f"{100 * out['overhead_reduction']:.0f}%")
    assert adaptive["total_overhead_s"] < fixed["total_overhead_s"], \
        "adaptive interval must beat the mis-tuned fixed interval"
    return out


# ------------------------------------------------------------ trace smoke
TRACE_PAYLOAD = 16 << 20
TRACE_PARTS = 8
TRACE_WORK_S = 60.0
TRACE_INTERVAL_S = 6.0
TRACE_OVERHEAD_TOL = 0.03


def _trace_leg(data, trace: bool, trace_path=None) -> dict:
    """One tracing leg: identical cluster + checkpoint workload, only the
    tracer differs.  Spans read the clocks but never advance the sim
    clock, so the traced leg's sim-time throughput must match the untraced
    one."""
    with ICheckCluster(n_icheck_nodes=2, n_spare_nodes=0,
                       node_memory=2 << 30, nic_bandwidth=1e9,
                       trace=trace, trace_path=trace_path) as c:
        client = ICheckClient("app", c.controller, ranks=TRACE_PARTS).init(
            ckpt_bytes_estimate=data.nbytes)
        client.add_adapt("x", data.shape, "float32", num_parts=TRACE_PARTS)
        parts = {"x": block_parts(data, TRACE_PARTS)}
        res = run_ckpt_workload(c, client, parts, TRACE_WORK_S, [],
                                interval_fn=lambda: TRACE_INTERVAL_S)
        res["spans"] = len(c.tracer.spans())
        client.finalize()
    return res


def run_trace_smoke(verbose: bool = True) -> dict:
    """B5T -- tracing overhead: sim-time throughput of a checkpointing
    workload with end-to-end tracing enabled must stay within
    ``TRACE_OVERHEAD_TOL`` of the untraced run, and the traced leg exports
    a Chrome ``trace_event`` artifact for Perfetto."""
    data = np.random.default_rng(2).standard_normal(
        TRACE_PAYLOAD // 4).astype(np.float32)
    os.makedirs(OBS_DIR, exist_ok=True)
    trace_path = os.path.abspath(os.path.join(OBS_DIR, "trace_smoke.json"))
    base = _trace_leg(data, trace=False)
    traced = _trace_leg(data, trace=True, trace_path=trace_path)
    # sim throughput = work_s / elapsed_sim_s over the same work, so the
    # traced/untraced throughput ratio is the inverse elapsed ratio
    ratio = base["elapsed_sim_s"] / max(traced["elapsed_sim_s"], 1e-12)
    out = {
        "payload": TRACE_PAYLOAD,
        "base": base,
        "traced": traced,
        "throughput_ratio": ratio,
        "trace_path": trace_path,
    }
    save("b5t_trace_overhead", out)
    if verbose:
        print(f"\nB5T tracing overhead ({fmt_bytes(TRACE_PAYLOAD)} ckpt, "
              f"{TRACE_WORK_S:.0f}s of work):")
        print(f"  untraced: {base['elapsed_sim_s']:.3f}s sim, "
              f"{base['commits']} commits")
        print(f"  traced:   {traced['elapsed_sim_s']:.3f}s sim, "
              f"{traced['commits']} commits, {traced['spans']} spans")
        print(f"  throughput ratio (traced/untraced): {ratio:.4f}")
        print(f"  chrome trace: {trace_path}")
    assert traced["spans"] > 0, "tracing was enabled but produced no spans"
    assert abs(1.0 - ratio) <= TRACE_OVERHEAD_TOL, \
        (f"tracing changed sim-time throughput by "
         f"{100 * abs(1.0 - ratio):.2f}% "
         f"(> {100 * TRACE_OVERHEAD_TOL:.0f}% tolerance)")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--adaptive", action="store_true",
                    help="run the adaptive-interval wasted-work comparison")
    ap.add_argument("--trace-smoke", action="store_true",
                    help="run the tracing-overhead comparison")
    args = ap.parse_args(argv)
    if args.adaptive:
        run_adaptive()
    elif args.trace_smoke:
        run_trace_smoke()
    else:
        run()


if __name__ == "__main__":
    main()
