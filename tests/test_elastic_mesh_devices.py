"""Elastic mesh redistribution with REAL device-count changes: a pytree
sharded over a 4-device mesh is committed through iCheck agents and
re-materialized onto an 8-device mesh (and back down to 2), moving only the
needed slices (plan.mesh_moves).  Runs in a subprocess with 8 fake CPU
devices so the in-process test suite keeps seeing 1 device."""
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import sys
sys.path.insert(0, "src")
from repro.core import ICheckCluster, ICheckClient, snapshot_pytree
from repro.core import plan as planlib

def mesh_of(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))

rng = np.random.default_rng(0)
w = rng.standard_normal((64, 32)).astype(np.float32)
b = rng.standard_normal((64,)).astype(np.float32)

m4 = mesh_of(4)
tree = {"w": jax.device_put(w, NamedSharding(m4, P("data", None))),
        "b": jax.device_put(b, NamedSharding(m4, P("data")))}

with ICheckCluster(n_icheck_nodes=2) as cluster:
    client = ICheckClient("app", cluster.controller, ranks=4).init()
    snap = snapshot_pytree(tree, step=0)
    assert snap.regions["w"].meta.partition.num_parts == 4, \
        snap.regions["w"].meta.partition
    client.add_adapt_snapshot(snap)
    client.commit(0, {n: r.parts for n, r in snap.regions.items()},
                  blocking=True)

    for new_n in (8, 2):
        mN = mesh_of(new_n)
        new_tree = {}
        for name, leaf in tree.items():
            spec = P("data", None) if name == "w" else P("data")
            sh = NamedSharding(mN, spec)
            boxes = planlib.mesh_part_bounds(np.shape(leaf), sh)
            parts = client.redistribute_mesh(name, boxes)
            assert len(parts) == new_n, (name, len(parts))
            full = np.zeros(np.shape(leaf), np.float32)
            for idx, arr in parts.items():
                sl = tuple(slice(lo, hi) for lo, hi in boxes[idx])
                full[sl] = arr
            np.testing.assert_array_equal(full, np.asarray(leaf))
            new_tree[name] = jax.device_put(full, sh)
        assert len(new_tree["w"].sharding.device_set) == new_n
    client.finalize()
print("ELASTIC_MESH_OK")
"""


@pytest.mark.dryrun
def test_mesh_redistribution_across_device_counts():
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, cwd=ROOT,
                         timeout=300)
    assert "ELASTIC_MESH_OK" in out.stdout, out.stdout + out.stderr
