"""Each traffic mix's window at a tiny configuration on the CPU, through the
harness's test-only path; the command itself refuses to run without a TPU;
a new configuration, mix and metric are found by name; and a run whose
timed path is broken comes out not correct."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = ("q8delta-save", "raw-resume")
SEED = 2**31 + 12345          # more than 32 signed bits hold


def bench_with_tiny(traffic, bench=None):
    bench = json.loads(json.dumps(bench or harness.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))))
    bench["configs"].append({"name": "tiny",
                             "file": "chipbench/tests/tiny.json"})
    name = "tiny." + traffic
    bench["workloads"].append({"name": name, "config": "tiny",
                               "traffic": traffic, "chips": 1})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(name)
    return bench, name


def run_tiny(traffic, seconds=2.0, **kw):
    bench, name = bench_with_tiny(traffic)
    return harness.run_cell(bench, name, SEED, seconds, trace=False,
                            require_tpu=False, **kw)


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_window_on_cpu(traffic):
    out = run_tiny(traffic)
    assert out["correct"], out["checks"]
    want = {m for m in harness.load_json(
        os.path.join(ROOT, "chipbench", "traffic", traffic + ".json"))
        ["reports"]}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2.5-3b.q8delta-save", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


def test_command_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2.5-3b.q8delta-save", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONPATH=""))
    assert p.returncode != 0
    assert not _result_lines(p.stdout)


def _digests(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            if "__pycache__" in base:
                continue
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_found_by_name(tmp_path):
    """A new configuration, traffic mix and per-layer metric are new files
    and new entries; no file the benchmark has is edited."""
    src = os.path.join(ROOT, "chipbench")
    dst = tmp_path / "chipbench"
    shutil.copytree(src, dst, ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(dst)
    cfg = harness.load_json(os.path.join(src, "tests", "tiny.json"))
    cfg["num_hidden_layers"] = 1
    (dst / "configs" / "tiny-one.json").write_text(json.dumps(cfg))
    mix = harness.load_json(os.path.join(src, "traffic", "q8delta-save.json"))
    mix["cycle"]["steps"] = 3
    (dst / "traffic" / "save-every-3.json").write_text(json.dumps(mix))
    (dst / "metrics" / "saves_in_window.py").write_text(
        "def read(ctx):\n"
        "    return float(len(ctx['driver'].saves)) or None\n")
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny-one",
                             "file": "chipbench/configs/tiny-one.json"})
    bench["workloads"].append({"name": "tiny-one.save-every-3",
                               "config": "tiny-one",
                               "traffic": "save-every-3", "chips": 1})
    bench["per_layer"].append({"name": "saves_in_window", "unit": "saves",
                               "better": "higher", "source": "host_clock",
                               "layer": "client, agents and tiers",
                               "moves": "train_tokens_per_s",
                               "workloads": ["tiny-one.save-every-3"]})
    out = harness.run_cell(bench, "tiny-one.save-every-3", SEED, 1.0,
                           trace=True, require_tpu=False, root=str(dst))
    assert out["correct"], out["checks"]
    assert out["metrics"]["saves_in_window"]["value"] >= 1
    assert "breakdown" in out
    after = _digests(dst)
    assert {k: v for k, v in after.items() if k in before} == before


# ------------------------------------------------------ broken timed path
def _state_unchanged(monkeypatch):
    import repro.train.elastic as elastic

    real = elastic.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(elastic, "make_train_step", make)


def _half_batch(monkeypatch):
    import repro.train.elastic as elastic

    real = elastic.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: step(
            state, {n: v[:v.shape[0] // 2] for n, v in batch.items()})

    monkeypatch.setattr(elastic, "make_train_step", make)


def _altered_save(monkeypatch):
    """A checkpoint's values altered where the snapshot produces them."""
    import jax
    import repro.train.elastic as elastic

    real = elastic.snapshot_pytree

    def snap(tree, *a, **k):
        tree = jax.tree.map(
            lambda x: x * 1.01 if jax.numpy.issubdtype(
                x.dtype, jax.numpy.floating) else x, tree)
        return real(tree, *a, **k)

    monkeypatch.setattr(elastic, "snapshot_pytree", snap)


def _save_alters_state(monkeypatch):
    """A save that alters the live state it is given, in place, before it
    reads it: the checkpoint and the state agree, and are both wrong."""
    import repro.train.elastic as elastic

    real = elastic.snapshot_pytree

    def snap(tree, *a, **k):
        group = tree.params
        while isinstance(next(iter(group.values())), dict):
            group = next(iter(group.values()))
        name = next(iter(group))
        group[name] = group[name] * 1.01
        return real(tree, *a, **k)

    monkeypatch.setattr(elastic, "snapshot_pytree", snap)


def _altered_restore(monkeypatch):
    """A restored value altered where the restore produces it."""
    import jax
    import repro.train.elastic as elastic

    real = elastic.restore_pytree

    def restore(*a, **k):
        out = real(*a, **k)
        leaves, tdef = jax.tree_util.tree_flatten(out)
        leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1)
        return jax.tree_util.tree_unflatten(tdef, leaves)

    monkeypatch.setattr(elastic, "restore_pytree", restore)


FAULTS = [("q8delta-save", _state_unchanged), ("q8delta-save", _half_batch),
          ("q8delta-save", _altered_save),
          ("q8delta-save", _save_alters_state),
          ("raw-resume", _state_unchanged),
          ("raw-resume", _half_batch), ("raw-resume", _altered_restore)]


@pytest.mark.parametrize("traffic,fault", FAULTS,
                         ids=[f"{t}-{f.__name__[1:]}" for t, f in FAULTS])
def test_broken_timed_path_is_not_correct(monkeypatch, traffic, fault):
    fault(monkeypatch)
    out = run_tiny(traffic, seconds=1.0)
    assert not out["correct"], out["checks"]


def test_control_fails_what_the_program_passes():
    """The reference one precision down, and with half the batch, in the
    program's place: each fails a limit that the program meets."""
    from chipbench import calibrate

    cfg = harness.load_json(os.path.join(ROOT, "chipbench", "tests",
                                         "tiny.json"))
    mix = harness.load_json(os.path.join(ROOT, "chipbench", "traffic",
                                         "q8delta-save.json"))
    fam = harness.load_module(os.path.join(
        ROOT, "chipbench", "models", "dense_decoder.py"), "dense_decoder")
    s = calibrate.calibrate(cfg, mix, fam, [SEED, 7], 2, emit=lambda _: 0)
    lim = cfg["limits"]
    assert all(s[k]["lower"] <= lim[k] for k in lim)
    for tag in ("control", "half_batch"):
        assert any(s[k][tag] > lim[k] for k in lim), (tag, s)
    assert s["codec_control"]["half_scale_ratio"] > 1.0
    assert s["codec_control"]["leaves_differing"] > 0
