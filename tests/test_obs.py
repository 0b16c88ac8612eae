"""Observability: end-to-end checkpoint tracing, latency histograms, the
flight recorder, and the bounded audit log.

The load-bearing contract: one checkpoint's life — commit → encode → L1
put → L2 drain → L3 trickle → restore — is a *single connected span tree*
under one ``trace_id``, across every thread hand-off (agent inboxes, the
drain pool, the background lane) and across the failure paths (funnel
fallback, mid-window re-hydration, agent death).  An orphan span means a
context hand-off was dropped somewhere.
"""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

from repro.core import ICheckClient, ICheckCluster, PartitionScheme
from repro.core import events as E
from repro.core import plan as planlib
from repro.core.agent import Agent, AgentDead
from repro.core.events import AuditLog, Event, EventBus
from repro.core.simnet import SimClock
from repro.core.types import PartitionDesc
from repro.obs import FlightRecorder, TraceCollector, trace_id_for
from repro.obs.hist import LogHistogram


def _parts(arr, desc):
    return {i: p for i, p in enumerate(planlib.split_array(arr, desc))}


def _assert_connected(tracer, trace_id):
    """One root, zero orphans: every non-root span's parent exists in the
    same trace."""
    spans = tracer.spans(trace_id)
    assert spans, f"no spans for {trace_id}"
    ids = {s.span_id for s in spans}
    roots = [s for s in spans if s.parent_id is None]
    assert len(roots) == 1, \
        f"{trace_id}: expected one root, got {[s.name for s in roots]}"
    orphans = [s.name for s in spans
               if s.parent_id is not None and s.parent_id not in ids]
    assert not orphans, f"{trace_id}: orphan spans {orphans}"


def _assert_all_connected(tracer):
    for tid in tracer.trace_ids():
        _assert_connected(tracer, tid)


def _validate_chrome_trace(doc):
    """Schema check on Chrome ``trace_event`` JSON: metadata events name
    the process/thread lanes, complete ('X') events carry ts/dur and the
    span identity in args."""
    assert isinstance(doc, dict)
    assert "traceEvents" in doc and isinstance(doc["traceEvents"], list)
    assert doc.get("displayTimeUnit") in ("ms", "ns")
    saw_x = saw_meta = False
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("X", "M"), ev
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            saw_meta = True
            assert ev["name"] in ("process_name", "thread_name")
            assert isinstance(ev["args"]["name"], str)
        else:
            saw_x = True
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            assert isinstance(ev["args"]["trace_id"], str)
            assert isinstance(ev["args"]["span_id"], int)
    assert saw_x and saw_meta


# ------------------------------------------------------------------ e2e
def test_commit_to_restore_is_one_connected_trace(tmp_path):
    """The acceptance path: commit → encode → L1 put/store → L2 drain →
    L3 trickle → restore, all under trace_id app/c0, one root, no
    orphans — and the exported Chrome trace validates."""
    trace_path = str(tmp_path / "trace.json")
    data = np.arange(1 << 12, dtype=np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=4)
    with ICheckCluster(n_icheck_nodes=2, l3=True, trace=True,
                       trace_path=trace_path,
                       obs_dir=str(tmp_path / "obs")) as c:
        client = ICheckClient("app", c.controller, ranks=4).init()
        client.add_adapt("x", data.shape, "float32", num_parts=4)
        client.commit(0, {"x": _parts(data, desc)}, blocking=True)
        c.controller.wait_for_drains(timeout=60)
        c.controller.wait_for_uploads(timeout=60)
        meta, parts, level = client.restart()
        got = np.concatenate([parts["x"][i] for i in range(4)])
        np.testing.assert_array_equal(got, data)
        client.finalize()
        tracer = c.tracer

    tid = trace_id_for("app", 0)
    _assert_connected(tracer, tid)
    names = {s.name for s in tracer.spans(tid)}
    assert {"commit", "encode", "agent_put", "l1_store", "l2_drain",
            "l3_trickle", "restore"} <= names, names
    root = tracer.root_of(tid)
    commit = [s for s in tracer.spans(tid) if s.name == "commit"]
    assert len(commit) == 1 and commit[0].span_id == root
    # the cluster wrote the Chrome trace on close
    with open(trace_path) as f:
        doc = json.load(f)
    _validate_chrome_trace(doc)
    x_ids = {ev["args"]["trace_id"] for ev in doc["traceEvents"]
             if ev["ph"] == "X"}
    assert tid in x_ids


def test_restore_joins_trace_without_handoff():
    """A restore hours later has no threaded context: the restore span
    re-joins the commit's tree via the derived trace_id + root fallback."""
    data = np.arange(1 << 10, dtype=np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=2)
    with ICheckCluster(n_icheck_nodes=2, trace=True) as c:
        client = ICheckClient("app", c.controller, ranks=2).init()
        client.add_adapt("x", data.shape, "float32", num_parts=2)
        client.commit(0, {"x": _parts(data, desc)}, blocking=True,
                      drain=False)
        client.restart()
        client.finalize()
        tid = trace_id_for("app", 0)
        restores = [s for s in c.tracer.spans(tid) if s.name == "restore"]
        assert restores
        assert restores[0].parent_id == c.tracer.root_of(tid)
        _assert_connected(c.tracer, tid)


# ----------------------------------------------------------- failure paths
@pytest.fixture()
def traced_cluster(tmp_path):
    c = ICheckCluster(n_icheck_nodes=4, n_spare_nodes=1,
                      adaptive_interval=False, trace=True,
                      obs_dir=str(tmp_path / "obs"))
    yield c
    c.close()


def test_funnel_fallback_keeps_trace_connected(traced_cluster, monkeypatch):
    """Peer path dies mid-transfer → client funnel takes over: the
    fallback's spans still land in the checkpoint's tree (no orphans) and
    the controller ships exactly one flight-recorder dump."""
    c = traced_cluster
    rng = np.random.default_rng(5)
    data = rng.standard_normal(1 << 13).astype(np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=6)
    client = ICheckClient("app", c.controller, ranks=6).init()
    client.add_adapt("x", data.shape, "float32", num_parts=6)
    client.commit(0, {"x": _parts(data, desc)}, blocking=True, drain=False)

    def dead_read(self, *a, **kw):
        raise AgentDead(f"agent {self.agent_id} died mid-transfer")

    monkeypatch.setattr(Agent, "peer_read", dead_read)
    out = client.redistribute("x", 4, via="peer")
    oracle = planlib.split_array(data, desc.renumbered(4))
    for p in range(4):
        np.testing.assert_array_equal(out[p], oracle[p])

    tid = trace_id_for("app", 0)
    names = {s.name for s in c.tracer.spans(tid)}
    assert "redistribute_funnel" in names
    _assert_all_connected(c.tracer)
    # the REDISTRIBUTION_FALLBACK event auto-dumped the flight recorder
    assert len(c.flight.dumps) == 1
    (path,) = c.flight.dumps.values()
    with open(path) as f:
        dump = json.load(f)
    assert dump["reason"].startswith("fallback_app")
    assert any(r.get("event") == E.REDISTRIBUTION_FALLBACK
               for r in dump["events"])
    client.finalize()


def test_rehydrating_cutover_keeps_trace_connected(traced_cluster):
    """Mid-window re-hydration (non-delta codec commits inside the overlap
    window): overlap_open / redistribute_window / cutover spans all attach
    to the base checkpoint's tree."""
    c = traced_cluster
    rng = np.random.default_rng(12)
    data = rng.standard_normal(1 << 13).astype(np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=6,
                         block=512)
    client = ICheckClient("app", c.controller, ranks=6, codec="q8").init()
    client.add_adapt("x", data.shape, "float32",
                     scheme=PartitionScheme.BLOCK, num_parts=6, block=512)
    for step in range(2):
        if step:
            data[:700] += np.float32(step)
        client.commit(step, {"x": _parts(data, desc)}, blocking=True,
                      drain=False)
    handle = client.redistribute("x", 9, overlap=True)
    assert handle.wait(60)
    data[1000:1600] += np.float32(1.0)
    client.commit(2, {"x": _parts(data, desc)}, blocking=True, drain=False)
    handle.cutover()
    cut = [e for e in c.controller.events
           if e["event"] == E.CUTOVER_DONE][-1]
    assert cut["rehydrated"]
    all_names = {s.name for s in c.tracer.spans()}
    assert {"overlap_open", "cutover"} <= all_names
    _assert_all_connected(c.tracer)
    client.finalize()


def test_peer_redistribution_records_window_span(traced_cluster):
    """The stop-the-world peer path: the engine's window span and the
    client's redistribute_peer span both join the checkpoint's tree."""
    c = traced_cluster
    rng = np.random.default_rng(7)
    data = rng.standard_normal(1 << 13).astype(np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=6)
    client = ICheckClient("app", c.controller, ranks=6).init()
    client.add_adapt("x", data.shape, "float32", num_parts=6)
    client.commit(0, {"x": _parts(data, desc)}, blocking=True, drain=False)
    client.redistribute("x", 4, via="peer")
    done = [e for e in c.controller.events
            if e["event"] == E.REDISTRIBUTION_DONE][-1]
    assert done["via"] == "peer"
    tid = trace_id_for("app", 0)
    names = {s.name for s in c.tracer.spans(tid)}
    assert {"redistribute_peer", "redistribute_window"} <= names
    _assert_all_connected(c.tracer)
    client.finalize()


def test_agent_death_restart_keeps_trace_connected(traced_cluster):
    """Kill the primary replica's agent: the restart's failover reads
    still produce a connected restore under the checkpoint's trace."""
    from repro.core.policies import SchedulingPolicy

    class SpreadPolicy(SchedulingPolicy):
        name = "spread4"

        def place(self, nodes, app):
            return [(nv.node_id, 1) for nv in nodes[:4]]

    c = traced_cluster
    c.controller.policy = SpreadPolicy()     # replicas on distinct agents
    data = np.arange(1 << 12, dtype=np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=4)
    client = ICheckClient("app", c.controller, ranks=4,
                          replication=2).init()
    client.add_adapt("x", data.shape, "float32", num_parts=4)
    client.commit(0, {"x": _parts(data, desc)}, blocking=True, drain=False)
    primary = c.controller.agents_for("app")[0]
    c.fault.kill_agent(primary.agent_id)
    meta, parts, level = client.restart()
    assert level == "l1"
    got = np.concatenate([parts["x"][i] for i in range(4)])
    np.testing.assert_array_equal(got, data)
    _assert_all_connected(c.tracer)
    client.finalize()


# ------------------------------------------------------------- histograms
def test_log_histogram_quantiles_and_buckets():
    h = LogHistogram()
    for v in (0.001, 0.002, 0.004, 0.5, 0.5, 0.5, 4.0):
        h.observe(v)
    d = h.as_dict()
    assert d["count"] == 7
    assert d["sum"] == pytest.approx(5.507)
    assert d["p50"] <= d["p95"] <= d["p99"]
    assert 0.25 <= d["p50"] <= 1.0          # the 0.5 cluster's bucket
    rows = h.prometheus_rows()
    assert rows[-1][0] == "+Inf" and rows[-1][1] == 7.0
    cums = [c for _, c in rows]
    assert cums == sorted(cums), "bucket counts must be cumulative"
    # fixed bounds: the le labels never depend on the data
    assert [le for le, _ in rows] == \
        [le for le, _ in LogHistogram().prometheus_rows()]


def test_log_histogram_overflow_bucket():
    h = LogHistogram(lo_exp=0, hi_exp=2)      # bounds 1, 2, 4
    h.observe(100.0)                          # beyond every finite bound
    rows = h.prometheus_rows()
    assert rows[-2] == ("4", 0.0)
    assert rows[-1] == ("+Inf", 1.0)


def test_quantiles_in_snapshot_and_prometheus():
    data = np.arange(1 << 12, dtype=np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=4)
    with ICheckCluster(n_icheck_nodes=2) as c:
        client = ICheckClient("app", c.controller, ranks=4).init()
        client.add_adapt("x", data.shape, "float32", num_parts=4)
        for step in range(3):
            client.commit(step, {"x": _parts(data, desc)}, blocking=True)
        c.controller.wait_for_drains(timeout=60)
        client.restart()
        snap = c.telemetry.snapshot()
        app = snap["per_app"]["app"]
        for key in ("commit_latency_quantiles", "commit_bytes_quantiles",
                    "drain_quantiles", "restore_quantiles",
                    "cutover_stall_quantiles"):
            assert set(app[key]) >= {"count", "sum"}, key
        for key in ("commit_latency_quantiles", "drain_quantiles",
                    "restore_quantiles"):
            q = app[key]
            assert q["count"] > 0, key
            assert q["p50"] <= q["p95"] <= q["p99"], key
        assert "peer_hop_quantiles" in snap["cluster"]
        text = c.telemetry.prometheus()
        for fam in ("icheck_commit_seconds", "icheck_drain_seconds",
                    "icheck_restore_seconds"):
            assert f"# TYPE {fam} histogram" in text
            assert re.search(
                rf'{fam}_bucket{{app="app",le="\+Inf"}} \d+', text)
            assert f"{fam}_sum" in text and f"{fam}_count" in text
        client.finalize()


# ------------------------------------------------------------- prometheus
# the full text exposition grammar, strictly: name{label="value",...} value
_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:\\.|[^"\\\n])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:\\.|[^"\\\n])*")*\})?'
    r' [+-]?(\d+(\.\d+)?([eE][+-]?\d+)?|Inf)$')
_PROM_HELP = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* \S.*$")
_PROM_TYPE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (gauge|counter|histogram)$")


def test_prometheus_full_output_is_strictly_well_formed():
    data = np.arange(1 << 11, dtype=np.float32)
    desc = PartitionDesc(scheme=PartitionScheme.BLOCK, num_parts=2)
    with ICheckCluster(n_icheck_nodes=2, l3=True) as c:
        client = ICheckClient("app", c.controller, ranks=2).init()
        client.add_adapt("x", data.shape, "float32", num_parts=2)
        client.commit(0, {"x": _parts(data, desc)}, blocking=True)
        c.controller.wait_for_drains(timeout=60)
        c.controller.wait_for_uploads(timeout=60)
        text = c.telemetry.prometheus()
        client.finalize()
    assert text.endswith("\n")
    n_samples = 0
    for line in text.splitlines():
        if line.startswith("# HELP"):
            assert _PROM_HELP.match(line), line
        elif line.startswith("# TYPE"):
            assert _PROM_TYPE.match(line), line
        else:
            assert _PROM_SAMPLE.match(line), f"malformed sample: {line!r}"
            n_samples += 1
    assert n_samples > 50          # gauges + counters + bucket series


def test_prometheus_label_escaping():
    from repro.core.services.telemetry import _escape_label_value

    assert _escape_label_value('a"b') == 'a\\"b'
    assert _escape_label_value("a\\b") == "a\\\\b"
    assert _escape_label_value("a\nb") == "a\\nb"
    # the escaped form must satisfy the strict sample grammar
    val = _escape_label_value('x"y\\z\nw')
    assert _PROM_SAMPLE.match(f'icheck_test{{app="{val}"}} 1')


# --------------------------------------------------------- flight recorder
def test_flight_dump_exactly_once(tmp_path):
    fr = FlightRecorder(clock=SimClock(), out_dir=str(tmp_path))
    bus = EventBus(SimClock())
    bus.subscribe(fr.on_event)
    for i in range(3):
        bus.publish("commit_done", app="a", ckpt=i)
    p1 = fr.dump("my_crash", extra={"seed": 7})
    p2 = fr.dump("my_crash")          # second trigger, same red cause
    assert p1 == p2
    files = list(tmp_path.iterdir())
    assert len(files) == 1 and files[0].name == "flight_my_crash.json"
    with open(p1) as f:
        payload = json.load(f)
    assert payload["extra"]["seed"] == 7          # first dump wins
    assert [r["event"] for r in payload["events"]] == ["commit_done"] * 3
    # a different cause still gets its own dump
    assert fr.dump("other_crash") != p1
    assert len(fr.dumps) == 2


def test_flight_ring_is_bounded():
    fr = FlightRecorder(max_events=4, max_spans=2)
    clock = SimClock()
    for i in range(10):
        fr.on_event(Event(name=f"e{i}", sim_t=float(i)))
    assert fr.events_seen == 10
    recent = fr.recent_events()
    assert len(recent) == 4
    assert [r["event"] for r in recent] == ["e6", "e7", "e8", "e9"]
    tracer = TraceCollector(clock=clock, enabled=True)
    tracer.add_listener(fr.on_span)
    for i in range(5):
        tracer.record(f"s{i}", "t/c0", "trk")
    assert fr.spans_seen == 5
    assert [s["name"] for s in fr.recent_spans()] == ["s3", "s4"]


def test_flight_events_carry_trace_identity():
    clock = SimClock()
    fr = FlightRecorder(clock=clock)
    bus = EventBus(clock)
    tracer = TraceCollector(clock=clock, enabled=True)
    bus.tracer = tracer
    bus.subscribe(fr.on_event)
    with tracer.span("commit", "app/c0", "client/app", root=True):
        bus.publish("ckpt_committed", app="app", ckpt=0)
    (rec,) = fr.recent_events()
    assert rec["trace_id"] == "app/c0" and isinstance(rec["span_id"], int)
    # the audit-record shape stays byte-compatible: trace ids ride beside
    # the event, never inside as_record()
    ev = bus.publish("noop")
    assert "trace_id" not in ev.as_record()


# --------------------------------------------------------------- audit log
def test_audit_log_record_shape_is_byte_compatible():
    bus = EventBus(SimClock())
    log = AuditLog()
    bus.subscribe(log)
    bus.publish("ckpt_committed", app="a", ckpt=3)
    (rec,) = log.records
    # payload keys first, then event, then sim_t — the legacy dict order
    assert list(rec) == ["app", "ckpt", "event", "sim_t"]
    assert rec == {"app": "a", "ckpt": 3, "event": "ckpt_committed",
                   "sim_t": 0.0}


def test_audit_log_ring_bounds_and_dropped_counter():
    bus = EventBus(SimClock())
    log = AuditLog(maxlen=5)
    bus.subscribe(log)
    for i in range(12):
        bus.publish(f"ev{i}")
    assert len(log.records) == 5
    assert log.dropped == 7
    assert log.names() == [f"ev{i}" for i in range(7, 12)]


# ------------------------------------------------------------ no-op tracer
def test_disabled_tracer_is_a_noop():
    t = TraceCollector(enabled=False)
    assert t.record("x", "t/c0", "trk") is None
    assert t.current() is None
    with t.use(None):
        with t.span("y", "t/c0", "trk") as ctx:
            assert ctx is None
    assert t.spans() == [] and t.trace_ids() == []


def test_tracer_bounded_spans():
    t = TraceCollector(clock=SimClock(), enabled=True, max_spans=3)
    for i in range(5):
        t.record(f"s{i}", "t/c0", "trk")
    assert len(t.spans()) == 3 and t.dropped == 2
    assert t.to_chrome_trace()["otherData"]["dropped_spans"] == 2


# ------------------------------------------------------ wall-clock spans
def test_wall_clock_spans_nest_with_positive_durations():
    import time

    t = TraceCollector(enabled=True)
    with t.span("outer", "t/c0", "trk", root=True) as outer:
        time.sleep(0.002)
        with t.span("inner", "t/c0", "trk") as inner:
            time.sleep(0.002)
        t.record("after", "t/c0", "trk", sim_s=4.0)
    t.disable()
    by = {s.name: s for s in t.spans()}
    o, i, a = by["outer"], by["inner"], by["after"]
    assert i.parent_id == outer.span_id and a.parent_id == outer.span_id
    assert inner.parent_id == outer.span_id and o.parent_id is None
    assert o.dur_s >= 0.004 and i.dur_s >= 0.002
    assert o.t0 < i.t0 and i.t0 + i.dur_s <= o.t0 + o.dur_s
    assert i.t0 + i.dur_s <= a.t0 <= o.t0 + o.dur_s
    # a modeled duration is an argument; the record itself is an instant
    assert a.args["sim_s"] == 4.0 and a.dur_s == 0.0
    assert t.to_chrome_trace()["otherData"]["clock"] == "wall"


def test_disabled_collector_records_nothing_and_imports_no_jax():
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro.obs import TraceCollector\n"
        "t = TraceCollector()\n"
        "with t.span('a', 't/c0', 'trk') as ctx, "
        "t.timed('b', 't/c0', 'trk') as tm:\n"
        "    t.note(bytes=1)\n"
        "    assert ctx is None and t.record('c', 't/c0', 'trk') is None\n"
        "assert tm.seconds >= 0 and t.provisional('x') is None\n"
        "assert t.adopt('x/pending1', 't/c0') is None\n"
        "assert t.spans() == [] and t.trace_ids() == []\n"
        "t.enable(); t.disable()\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('ok')\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr


def test_forced_gc_is_a_gc_span():
    import gc

    t = TraceCollector(enabled=True)
    gc.collect()                      # outside any span: not attributed
    assert t.spans() == []
    with t.span("outer", "t/c0", "trk", root=True) as outer:
        gc.collect()
    t.disable()
    gcs = [s for s in t.spans() if s.name == "gc"]
    assert gcs and gcs[-1].args["generation"] == 2
    assert all(isinstance(s.args["collected"], int) for s in gcs)
    assert all(s.trace_id == "t/c0" and s.parent_id == outer.span_id
               and s.dur_s > 0 for s in gcs)
    n = len(t.spans())
    with t.use(outer):
        gc.collect()                  # the hook went with disable()
    assert len(t.spans()) == n


def _traced_q8_delta_saves(tmp_path):
    import jax.numpy as jnp

    from repro.core import snapshot_pytree

    rng = np.random.default_rng(3)
    tree = {"w": jnp.asarray(rng.standard_normal((8, 256)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal(300), jnp.float32),
            "n": jnp.asarray([7, 9], jnp.int32)}
    c = ICheckCluster(n_icheck_nodes=2, trace=True,
                      obs_dir=str(tmp_path / "obs"))
    client = ICheckClient("app", c.controller, ranks=1,
                          codec="q8-delta").init()
    snaps = []
    for step in range(2):
        snap = snapshot_pytree(tree, step=step, codec="q8-delta",
                               chain_lookup=client.delta_chain_lookup,
                               tracer=c.tracer)
        client.commit_snapshot(snap, blocking=True, drain=False)
        snaps.append(snap)
        # AdamW-like churn: every value moves, so every block changes
        tree = {k: (v * 1.5 + 0.25 if v.dtype == jnp.float32 else v)
                for k, v in tree.items()}
    return c, client, snaps


def test_traced_q8_delta_save_is_one_connected_tree(tmp_path):
    c, client, snaps = _traced_q8_delta_saves(tmp_path)
    try:
        _assert_all_connected(c.tracer)
        tid = trace_id_for("app", 1)
        spans = c.tracer.spans(tid)
        by_id = {s.span_id: s for s in spans}
        names = {s.name for s in spans}
        assert {"commit", "commit/catalog", "encode", "l1_commit",
                "agent_put", "snapshot", "snapshot/encode",
                "snapshot/d2h_wait", "snapshot/xor",
                "snapshot/frame"} <= names, names
        (root,) = [s for s in spans if s.parent_id is None]
        assert root.name == "commit" and root.args["ckpt"] == 1
        (snap,) = [s for s in spans if s.name == "snapshot"]
        assert snap.parent_id == root.span_id
        assert snap.args == {"step": 1, "codec": "q8-delta", "regions": 3}
        for s in spans:
            if s.name.startswith("snapshot/"):
                assert by_id[s.parent_id].name == "snapshot"
                assert snap.t0 <= s.t0 and s.t0 + s.dur_s <= \
                    snap.t0 + snap.dur_s
        # the frames the spans report are the frames that were shipped
        shipped = c.controller.app("app").checkpoints[1].regions
        frames = {s.args["region"]: s.args for s in spans
                  if s.name == "snapshot/frame"}
        assert set(frames) == {"w", "b"}
        for name, args in frames.items():
            assert args["frame"] == shipped[name].frame == "key"
            assert args["frame"] == snaps[1].regions[name].encoded.frame
            assert args["delta_built"] and args["delta_discarded"]
            assert args["bytes"] == sum(
                len(b) for b in snaps[1].regions[name].encoded.blobs.values())
        puts = [s for s in spans if s.name == "agent_put"]
        assert puts and all(s.args["bytes"] > 0 for s in puts)
        d2h = [s for s in spans if s.name == "snapshot/d2h_wait"]
        assert {s.args["region"] for s in d2h} == {"w", "b", "n"}
        assert all(s.args["bytes"] > 0 for s in d2h)
        # the encode time the codec gauge reads is the spans' own time
        enc = snaps[1].regions["w"].encoded.encode_s
        phases = sum(s.dur_s for s in spans if s.name.startswith(
            "snapshot/") and s.args["region"] == "w")
        assert enc == pytest.approx(phases, rel=1e-9)
        # the first save is a keyframe: nothing built, nothing discarded
        first = [s.args for s in c.tracer.spans(trace_id_for("app", 0))
                 if s.name == "snapshot/frame"]
        assert first and not any(a["delta_built"] for a in first)
        assert c.tracer.to_chrome_trace()["otherData"]["clock"] == "wall"
    finally:
        client.finalize()
        c.close()


def test_traced_resume_gives_trainer_init_and_restore_spans():
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.train import ElasticTrainer

    cfg = get_config("qwen2.5-3b", tiny=True)
    shape = ShapeConfig("t", "train", 32, 4)
    with ICheckCluster(n_icheck_nodes=2, trace=True) as c:
        kw = dict(app_id="app", seed=3, commit_every=0, probe_every=0,
                  total_steps=4)
        t1 = ElasticTrainer(cfg, shape, c, **kw)
        assert not t1.restarted
        t1.commit(blocking=True)
        t2 = ElasticTrainer(cfg, shape, c, **kw)
        assert t2.restarted
        t2.finalize()
        c.controller.wait_for_drains(timeout=60)   # no span left open
        spans = c.tracer.spans()
        _assert_all_connected(c.tracer)
    mine = [s for s in spans if s.trace_id == t2.trace_id]
    names = {s.name for s in mine}
    assert {"trainer_init", "trainer_init/state", "trainer_init/register",
            "restart", "snapshot"} <= names, names
    (init,) = [s for s in mine if s.name == "trainer_init"]
    (restart,) = [s for s in mine if s.name == "restart"]
    assert init.t0 + init.dur_s <= restart.t0
    reg = [s for s in mine if s.name == "trainer_init/register"][0]
    assert reg.args["bytes"] > 0
    ckpt = [s for s in spans if s.trace_id == trace_id_for("app", 0)]
    got = {s.name for s in ckpt}
    assert {"restore", "restore/fetch", "restore/decode", "restore/place",
            "restore/assemble", "restore/h2d"} <= got, got
    place = [s for s in ckpt if s.name == "restore/place"][-1]
    for s in ckpt:
        if s.name in ("restore/assemble", "restore/h2d") \
                and s.t0 >= restart.t0:
            assert s.parent_id == place.span_id and s.args["bytes"] > 0
    for s in ckpt:
        if s.name in ("restore/place", "restore") and s.t0 >= restart.t0:
            assert restart.t0 <= s.t0
            assert s.t0 + s.dur_s <= restart.t0 + restart.dur_s


def test_profiler_events_share_the_collector_clock(tmp_path):
    """Under ``jax.profiler``, each ``icheck/`` host event of the
    ``.xplane.pb`` lasts as long as its collector span, at one constant
    offset between the two clocks."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData

    t = TraceCollector(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        t.enable(annotate=True)
        for i, pause in enumerate((0.004, 0.012, 0.002, 0.02)):
            with t.span(f"outer{i}", "t/c0", "trk"):
                time.sleep(pause)
                with t.span(f"inner{i}", "t/c0", "trk"):
                    time.sleep(pause / 2)
            time.sleep(0.003)
        t.disable()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("icheck/"):
                    events[e.name[len("icheck/"):]] = e
    spans = {s.name: s for s in t.spans() if s.name != "gc"}
    assert set(spans) <= set(events) and len(spans) == 8
    offsets = []
    for name, s in spans.items():
        e = events[name]
        assert abs(e.duration_ns * 1e-9 - s.dur_s) < 1e-3, name
        offsets.append(e.start_ns * 1e-9 - s.t0)
    assert max(offsets) - min(offsets) < 1e-3


def test_provisional_traces_under_thread_contention():
    """Many threads at once, each a commit-shaped flow — a snapshot under a
    provisional trace, then a root span whose trace is named mid-span and
    adopts the snapshot — with garbage collections forced among them:
    every trace comes out whole, connected and named."""
    import gc
    import sys
    import threading

    t = TraceCollector(enabled=True)
    n_threads, rounds = 16, 20
    errors = []

    def flow(k):
        try:
            for r in range(rounds):
                snap_tid = t.provisional("snap")
                with t.span("snapshot", snap_tid, "trk", root=True) as snap:
                    with t.span("snapshot/frame", snap_tid, "trk"):
                        if r % 5 == 0:
                            gc.collect(0)
                prov = t.provisional("app")
                with t.span("commit", prov, "trk", root=True):
                    with t.span("commit/catalog", prov, "trk"):
                        pass
                    tid = trace_id_for(f"a{k}", r)
                    root = t.adopt(prov, tid)
                    t.adopt(snap.trace_id, tid, parent=root)
                    with t.span("encode", tid, "trk"):
                        t.note(n=r)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=flow, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
        t.disable()
    assert not errors, errors
    assert not any("pending" in tid for tid in t.trace_ids())
    for k in range(n_threads):
        for r in range(rounds):
            tid = trace_id_for(f"a{k}", r)
            _assert_connected(t, tid)
            names = sorted(s.name for s in t.spans(tid) if s.name != "gc")
            assert names == ["commit", "commit/catalog", "encode",
                             "snapshot", "snapshot/frame"], (tid, names)
