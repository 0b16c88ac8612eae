"""Runs one cell once: finds its configuration, traffic mix and metric
readers by the names in ``BENCHMARK.json``, sets up, measures, checks the
result against the plain reference, and returns the result line.

Files are found by name, so a new cell needs only new files:
``configs/<config>.json`` (sizes, cuts, cluster, limits; its ``family``
names ``models/<family>.py``, the plain reference and the seeded weights),
``traffic/<traffic>.json`` (read by the one driver) and
``metrics/<metric>.py`` (a ``read(ctx)`` that returns a number or None).
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, Optional

from . import checks
from .driver import Driver, note, peak_rss, stderr
from .peaks import peaks
from .spans import Spans

HERE = os.path.dirname(os.path.abspath(__file__))


def process_start() -> float:
    """This process's start on ``time.perf_counter``'s clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        age = up - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - age
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise SystemExit(f"no file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    if not os.path.exists(path):
        raise SystemExit(f"no file {path}")
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` with everything its name leads to."""

    def __init__(self, bench: dict, workload: str, root: str = HERE):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.spec = cells[workload]
        self.name = workload
        configs = {c["name"]: c for c in bench["configs"]}
        cspec = configs[self.spec["config"]]
        self.cfg = load_json(os.path.join(os.path.dirname(root),
                                          cspec["file"]))
        self.traffic = load_json(os.path.join(
            root, "traffic", self.spec["traffic"] + ".json"))
        self.family = load_module(
            os.path.join(root, "models", self.cfg["family"] + ".py"),
            self.cfg["family"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench.get("per_layer", [])
            if workload in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in reported)]
        self.readers = {m["name"]: load_module(
            os.path.join(root, "metrics", m["name"] + ".py"), m["name"])
            for m in self.per_layer}
        self.chips = self.spec["chips"]


# --------------------------------------------------------- end to end
def end_to_end(name: str, d: Driver, t_start: float) -> Optional[float]:
    tok = d.fam.tokens_per_step(d.cfg)
    if name == "setup_s":
        return d.w0 - t_start
    if name == "train_tokens_per_s":
        return d.window_steps * tok / (d.w1 - d.w0)
    if name == "save_stall_s":
        return statistics.fmean(s.back - s.call for s in d.saves) \
            if d.saves else None
    if name == "commit_durable_s":
        done = [s.done - s.call for s in d.saves if s.done is not None]
        return statistics.fmean(done) if done else None
    if name == "resume_s":
        return statistics.fmean(r.first_loss - r.start
                                for r in d.resumes) if d.resumes else None
    raise SystemExit(f"no definition of the end-to-end metric {name!r}")


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's backend is {devs[0].platform!r}; "
                         f"this benchmark runs on the chip only")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[:chips]]
    return int(max(vals))


def _count_compiles() -> list:
    """A list that grows by one at every compile the persistent cache did
    not serve (JAX reports a compile event for hits and misses alike, and
    a saved-time event for each hit)."""
    import jax

    seen: list = []

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(1)
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            seen.append(-1)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen


def _wrap_program(spans: Spans) -> None:
    """Spans around the program's layers, in the traced run only."""
    import repro.train.elastic as elastic
    from repro.core.client import ICheckClient

    def frames(snap):
        out = {}
        for name, r in snap.regions.items():
            if r.encoded is not None:
                n = r.encoded.raw_nbytes // 4
                out[name] = (r.encoded.frame or "key", n)
        return {"frames": out}

    spans.wrap(elastic, "snapshot_pytree", "snapshot", keep=frames)
    spans.wrap(elastic.ElasticTrainer, "restart_if_available", "restart")
    spans.wrap(ICheckClient, "restart", "restart_fetch")


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             t_start: Optional[float] = None, root: str = HERE) -> dict:
    """One run of one cell; returns the result line's object."""
    t_start = process_start() if t_start is None else t_start
    cell = Cell(bench, workload, root)
    device = device_info(cell.chips, require_tpu)
    pk = peaks(device["kind"]) if require_tpu else None
    spans = Spans(annotate=trace)
    if trace:
        _wrap_program(spans)
    d = Driver(cell.cfg, cell.traffic, cell.family, seed, spans)
    compiles = _count_compiles()
    d.setup()
    prof_dir = None
    if trace:
        import jax
        prof_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
    before = len(compiles)
    with spans.span("window"):
        d.window(seconds)
    d.window_compiles = sum(compiles[before:])
    if trace:
        import jax
        jax.profiler.stop_trace()
    d.await_saves()
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    t_check = time.perf_counter()
    d.save_checks()
    if cell.traffic["cycle"].get("commit"):
        d.read_back()
    else:
        d.restore_checks()
    note(f"read-back: {time.perf_counter() - t_check:.1f} s")
    spans.unwrap()
    metrics: Dict[str, dict] = {}
    extra: Dict[str, object] = {}
    if not trace:
        for m in cell.end_to_end:
            v = end_to_end(m["name"], d, t_start)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from . import trace as tracemod

        red = tracemod.read(tracemod.find_xplane(prof_dir))
        shutil.rmtree(prof_dir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        extra["breakdown"] = red.breakdown()
        ctx = {"cfg": d.cfg, "family": d.fam, "peaks": pk,
               "chips": cell.chips, "spans": spans, "driver": d,
               "trace": red}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    _report_run(d, spans)
    d.release()
    t_ref = time.perf_counter()
    ref = d.reference()
    d.checks.update(d.compare(ref))
    note(f"reference: {time.perf_counter() - t_ref:.1f} s")
    limits = checks_limits(cell.cfg, cell.traffic)
    judged = checks.judge(d.checks, limits)
    correct = all(j["ok"] for j in judged.values())
    out = {"correct": correct, "attempted": d.attempted, "failed": d.failed,
           "metrics": metrics, "device": device}
    out.update(extra)
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in judged.items()}
    for k, v in judged.items():
        stderr(f"check {k}: {v['value']!r} limit {v['limit']!r}"
               f"{'' if v['ok'] else '  FAILED'}")
    return out


def checks_limits(cfg: dict, traffic: dict) -> Dict[str, float]:
    """Each compared number's limit: the training numbers' from the
    configuration (set from chip readings), the rest stated guarantees."""
    lim = {k: (float("nan") if v is None else v)
           for k, v in cfg["limits"].items()}
    lim.update(restored_step_gap=0, leaves_differing=0, cursor_differing=0,
               losses_differing=0, state_changed_by_save=0)
    if traffic["codec"].startswith("q8"):
        lim["half_scale_ratio"] = checks.HALF_SCALE_LIMIT
    return lim


def _resume_split(d: Driver, spans: Spans) -> list:
    """Each window resume in parts, where the traced run's spans give
    them: trainer init, L1 fetch, placement, first step."""
    from .spans import nested

    out = []
    for r in d.resumes:
        inner = {i.name: i for name in ("restart", "restart_fetch")
                 for _, i in nested(spans, "resume", name, r.start, r.built)}
        if len(inner) == 2:
            rs, f = inner["restart"], inner["restart_fetch"]
            out.append({"init_s": rs.start - r.start, "fetch_s": f.seconds,
                        "place_s": rs.seconds - f.seconds,
                        "first_step_s": r.first_loss - r.built})
    return out


def _report_run(d: Driver, spans: Spans) -> None:
    """Single-run facts for the reader, on lines before the result."""
    note("run: " + json.dumps({
        "window_s": d.w1 - d.w0, "steps": d.window_steps,
        "window_compiles": d.window_compiles,
        "saves": [{"step": s.step, "stall_s": s.back - s.call,
                   "durable_s": None if s.done is None else s.done - s.call}
                  for s in d.saves],
        "resumes": [{"resume_s": r.first_loss - r.start,
                     "build_s": r.built - r.start} for r in d.resumes],
        "resume_split": _resume_split(d, spans),
        "rss_per_cycle": d.rss_per_cycle, "host_peak_rss": peak_rss(),
        "losses": d.losses[-12:]}))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = process_start()
    bench = load_json(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"))
    try:
        import jax
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        raise SystemExit(f"cannot import the program under test: {e}")

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=t_start)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
