"""Registering a trainer's regions without copying its state to the host.

``region_metas`` reads every leaf's raw region from its shape, dtype and
sharding; it must equal, field for field, the region a raw
``snapshot_pytree`` of the same state builds after its D2H copy.
``ElasticTrainer`` registers from those metas, and a checkpoint chain it
starts must still restore: bit for bit with the raw codec, within half a
block scale with q8-delta.  The way back, ``place_leaf``, rebuilds a leaf
from its parts' boxes under a target sharding.
"""
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core import ICheckCluster, region_metas, snapshot_pytree
from repro.core import plan as planlib
from repro.core.snapshot import leaf_names, place_leaf
from repro.core.types import RegionMeta
from repro.kernels.ckpt_codec.blocks import BLOCK
from repro.optim import AdamWConfig
from repro.train import ElasticTrainer
from repro.train import elastic
from repro.train.state import make_train_state

ROOT = Path(__file__).resolve().parents[1]


def _raw_snapshot_metas(tree):
    snap = snapshot_pytree(tree)
    for sr in snap.regions.values():
        # the snapshot's meta against its own host parts, not the helper
        # both may share
        assert sr.meta.nbytes == sum(p.nbytes for p in sr.parts.values())
        assert {str(p.dtype) for p in sr.parts.values()} == {sr.meta.dtype}
    return {name: sr.meta for name, sr in snap.regions.items()}


@pytest.mark.parametrize("arch,leaves", [
    ("qwen2.5-3b", ("params/stack/b0/attn/wkv", "params/stack/b0/attn/bkv")),
    ("deepseek-7b", ("params/stack/b0/attn/wkv",)),
    ("deepseek-v2-lite", ("params/stack/b0/moe/w_gu",
                          "opt/nu/stack/b0/moe/w_down",
                          "params/stack/b0/attn/wkv_a",
                          "params/lead0/attn/kv_norm/scale")),
])
def test_region_metas_equal_raw_snapshot_metas(arch, leaves):
    """Dense GQA, dense MHA, and latent attention with experts: every
    leaf, the int32 ``step`` included."""
    cfg = get_config(arch, tiny=True)
    state = make_train_state(cfg, jax.random.key(0), AdamWConfig())
    got = region_metas(state)
    assert got == _raw_snapshot_metas(state)
    assert list(got) == leaf_names(state)
    assert set(leaves) <= set(got)
    assert got["step"].shape == () and got["step"].dtype == "int32"
    assert got["step"].nbytes == 4


@pytest.mark.parametrize("boxes", [
    (((0, 6), (0, 8)),),
    (((0, 2), (0, 8)), ((2, 6), (0, 8))),
    (((0, 6), (0, 5)), ((0, 6), (5, 8))),
], ids=["one-box", "rows-in-two-boxes", "columns-in-two-boxes"])
def test_place_leaf_rebuilds_boxed_parts(boxes):
    """Parts cut at their boxes come back as the whole leaf, cast to the
    target dtype, on the replicated sharding asked for."""
    x = np.arange(48, dtype=np.float32).reshape(6, 8) / 7
    parts = {i: x[tuple(slice(lo, hi) for lo, hi in box)].ravel()
             for i, box in enumerate(boxes)}
    meta = RegionMeta(name="w", shape=x.shape, dtype="float32",
                      partition=planlib.boxes_to_desc(x.shape, boxes),
                      nbytes=x.nbytes)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    got = place_leaf(parts, meta, jax.numpy.bfloat16, rep)
    assert got.sharding == rep and got.dtype == jax.numpy.bfloat16
    np.testing.assert_array_equal(np.asarray(got),
                                  x.astype(jax.numpy.bfloat16))


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import region_metas, snapshot_pytree

assert len(jax.devices()) == 4
mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("data", "model"))
rng = np.random.default_rng(0)

def put(x, spec):
    return jax.device_put(x, NamedSharding(mesh, spec))

tree = {
    "sharded": put(rng.standard_normal((64, 32), np.float32), P("data", "model")),
    "rows": put(rng.standard_normal((8, 6), np.float32), P("data", None)),
    "bf16": put(jnp.ones((16, 8), jnp.bfloat16), P(None, "model")),
    "replicated": put(rng.standard_normal((5, 3), np.float32), P()),
    "step": put(np.int32(7), P()),
}
want = snapshot_pytree(tree)
got = region_metas(tree)
for name, sr in want.regions.items():
    assert got[name] == sr.meta, (name, got[name], sr.meta)
    assert sr.meta.nbytes == sum(p.nbytes for p in sr.parts.values()), name
assert set(got) == set(want.regions)
assert got["sharded"].partition.num_parts == 4
assert got["sharded"].nbytes == 64 * 32 * 4
assert got["rows"].partition.num_parts == 2
assert got["bf16"].dtype == "bfloat16" and got["bf16"].nbytes == 16 * 8 * 2
# replicas are one part, counted once
assert got["replicated"].partition.num_parts == 1
assert got["replicated"].nbytes == 5 * 3 * 4
assert got["step"].nbytes == 4
print("REGION_METAS_MESH_OK")
"""


def test_region_metas_on_a_four_device_mesh():
    """Sharded, partly replicated and replicated leaves on a 2x2 mesh of
    fake CPU devices, in a subprocess so the suite keeps one device."""
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                         capture_output=True, text=True, cwd=ROOT,
                         timeout=300)
    assert "REGION_METAS_MESH_OK" in out.stdout, out.stdout + out.stderr


def _trainer_without_a_snapshot(monkeypatch, *args, **kw):
    """An ``ElasticTrainer`` built while the trainer's ``snapshot_pytree``
    refuses to run: its construction (and restart) copies no state to the
    host to register it."""
    def refuse(*a, **k):
        raise AssertionError("trainer construction took a snapshot")

    with monkeypatch.context() as m:
        m.setattr(elastic, "snapshot_pytree", refuse)
        return ElasticTrainer(*args, **kw)


def _host_state(state) -> dict:
    return {name: np.asarray(leaf)
            for name, leaf in zip(leaf_names(state), jax.tree.leaves(state))}


def _half_scale_excess(saved: np.ndarray, restored: np.ndarray) -> float:
    """Largest ``|restored - saved|`` over its 256-value block's half
    scale (``absmax / 254``, plus one f32 ulp): <= 1 is within it."""
    x = np.ravel(saved).astype(np.float64)
    y = np.ravel(restored).astype(np.float64)
    pad = (-x.size) % BLOCK
    xb = np.pad(x, (0, pad)).reshape(-1, BLOCK)
    yb = np.pad(y, (0, pad)).reshape(-1, BLOCK)
    absmax = np.max(np.abs(xb), axis=1, keepdims=True)
    bound = absmax / 254 + np.spacing(absmax.astype(np.float32))
    return float(np.max(np.abs(yb - xb) / bound))


@pytest.mark.parametrize("codec", ["raw", "q8-delta"])
def test_trainer_chain_starts_from_registered_metas(monkeypatch, codec):
    """A trainer registered from metas commits; a new trainer, also
    registered from metas, restarts from that checkpoint.  Raw: both go on
    bit for bit.  q8-delta: the first commit is a keyframe, the restore
    is within half a block scale and integer leaves are exact."""
    cfg = get_config("qwen2.5-3b", tiny=True)
    shape = ShapeConfig("t", "train", 32, 4)
    kw = dict(app_id="app", seed=3, opt_cfg=AdamWConfig(lr=1e-3),
              commit_every=0, probe_every=0, total_steps=10, codec=codec)
    with ICheckCluster(n_icheck_nodes=2, trace=True) as c:
        t1 = _trainer_without_a_snapshot(monkeypatch, cfg, shape, c, **kw)
        assert not t1.restarted
        t1.run(3)
        saved = _host_state(t1.state)
        h = t1.commit(blocking=True)
        t2 = _trainer_without_a_snapshot(monkeypatch, cfg, shape, c, **kw)
        assert t2.restarted and int(t2.state.step) == 3
        for t in (t1, t2):
            (reg,) = [s for s in c.tracer.spans(t.trace_id)
                      if s.name == "trainer_init/register"]
            assert reg.args["host_bytes"] == 0
            assert reg.args["bytes"] == sum(a.nbytes
                                            for a in saved.values())
            assert reg.args["regions"] == len(saved)
        restored = _host_state(t2.state)
        if codec == "raw":
            for name, a in saved.items():
                np.testing.assert_array_equal(restored[name], a, err_msg=name)
            t1.run(3)
            t2.run(3)
            assert [m["loss"] for m in t2.metrics_log] == \
                [m["loss"] for m in t1.metrics_log[3:]]
            for a, b in zip(jax.tree.leaves(t1.state),
                            jax.tree.leaves(t2.state)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            for name, a in saved.items():
                region = h.meta.regions[name]
                if np.issubdtype(a.dtype, np.floating):
                    assert region.frame == "key", (name, region.frame)
                    assert region.chain == (h.ckpt_id,), name
                    assert _half_scale_excess(a, restored[name]) <= 1.0, name
                else:
                    np.testing.assert_array_equal(restored[name], a,
                                                  err_msg=name)
        t2.finalize()
        c.controller.wait_for_drains(timeout=60)
