"""HLO analyzer: while-loop trip scaling, dot FLOP counting, collective
parsing -- validated against modules with known costs."""
import subprocess
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo import analyze, parse_hlo, top_instructions

ROOT = Path(__file__).resolve().parents[1]


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_plain_matmul_flops():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    txt = _compile_text(lambda a, b: a @ b, a, a)
    res = analyze(txt)
    assert abs(res["flops"] - 2 * 256**3) / (2 * 256**3) < 0.05


def test_scan_multiplies_flops():
    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def f(a, b):
        def body(x, _):
            return jnp.tanh(x @ b), None
        x, _ = jax.lax.scan(body, a, None, length=10)
        return x

    txt = _compile_text(f, a, a)
    res = analyze(txt)
    expect = 10 * 2 * 128**3
    assert abs(res["flops"] - expect) / expect < 0.1, res["flops"]


def test_nested_scan_multiplies():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a, b):
        def outer(x, _):
            def inner(y, _):
                return y @ b, None
            y, _ = jax.lax.scan(inner, x, None, length=4)
            return jnp.tanh(y), None
        x, _ = jax.lax.scan(outer, a, None, length=3)
        return x

    txt = _compile_text(f, a, a)
    res = analyze(txt)
    expect = 12 * 2 * 64**3
    assert abs(res["flops"] - expect) / expect < 0.15, res["flops"]


def test_bytes_reasonable_for_elementwise():
    a = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)
    txt = _compile_text(lambda x: x * 2 + 1, a)
    res = analyze(txt)
    # one pass read + write = 8 MiB; fusion counting should be within 2x
    assert 4e6 < res["bytes"] < 3.2e7, res["bytes"]


def test_parse_computations_and_tops():
    a = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    txt = _compile_text(lambda a, b: jnp.tanh(a @ b) @ b, a, a)
    comps = parse_hlo(txt)
    assert any(i.opcode == "dot" for c in comps.values() for i in c.instrs)
    tops = top_instructions(txt, 3)
    assert len(tops["flops"]) >= 1
    assert tops["flops"][0][0] > 0


SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((8,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
sh = NamedSharding(mesh, P(None, "model"))
f = jax.jit(lambda a, b: (a @ b).sum(), in_shardings=(None, sh))
a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
txt = f.lower(a, a).compile().as_text()
import sys; sys.path.insert(0, "src")
from repro.launch.hlo import analyze
res = analyze(txt)
assert res["collectives"]["total_link_bytes"] > 0, res
print("COLLECTIVES_OK", res["collectives"]["counts"])
"""


@pytest.mark.dryrun
def test_collectives_detected_in_sharded_module():
    out = subprocess.run([sys.executable, "-c", SUB], capture_output=True,
                         text=True, cwd=ROOT, timeout=180)
    assert "COLLECTIVES_OK" in out.stdout, out.stdout + out.stderr
