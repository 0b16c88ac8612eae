"""ckpt_codec kernel: shape/dtype sweeps vs the jnp oracle + properties."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ckpt_codec import (BLOCK, dequantize, quantize,
                                      quantize_delta, undelta_dequantize)
from repro.kernels.ckpt_codec.blocks import quantize_np
from repro.kernels.ckpt_codec.ops import _to_blocks
from repro.kernels.ckpt_codec.ref import quantize_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("n", [1, 255, 256, 257, 4096, 100_000])
@pytest.mark.parametrize("dtype", [np.float32, np.float16, jnp.bfloat16])
def test_quantize_matches_ref(n, dtype):
    x = jnp.asarray(RNG.standard_normal(n)).astype(dtype)
    q_i, s_i = quantize(x, impl="interpret")
    blocks, _ = _to_blocks(x)
    q_r, s_r = quantize_ref(blocks)
    # XLA may fuse x/scale as x*(1/scale): round-to-nearest ties can move
    # a code by at most 1 ulp of the int8 grid
    diff = np.abs(np.asarray(q_i, np.int32) - np.asarray(q_r, np.int32))
    assert diff.max() <= 1
    assert (diff != 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s_i), np.asarray(s_r), rtol=1e-6)


@pytest.mark.parametrize("shape", [(17,), (33, 65), (4, 5, 6)])
def test_roundtrip_error_bound(shape):
    x = RNG.standard_normal(shape).astype(np.float32) * 10
    for impl in ("interpret", "xla"):
        q, s = quantize(x, impl=impl)
        xr = dequantize(q, s, shape, jnp.float32, impl=impl)
        # per-block error bounded by scale/2 = absmax/254
        err = np.abs(np.asarray(xr) - x)
        assert err.max() <= np.abs(x).max() / 127 * 0.51 + 1e-7


def _midpoint_block():
    """A block whose value x lies just past the midpoint (k + 1/2) * scale
    of two codes, while the f32 quotient x / scale rounds to k + 1/2
    exactly: rounding that quotient (half to even, k even) picks k, more
    than half a scale away from x."""
    rng = np.random.default_rng(1)
    while True:
        absmax = np.float32(rng.uniform(0.5, 2.0))
        scale = absmax / np.float32(127.0)
        k = 2 * int(rng.integers(1, 63))
        mid = (k + 0.5) * np.float64(scale)        # exact in float64
        x = np.float32(mid)
        if x <= mid:
            x = np.nextafter(x, np.float32(np.inf))
        if x / scale == np.float32(k + 0.5):
            block = np.zeros((1, BLOCK), np.float32)
            block[0, 0], block[0, 1] = absmax, x
            return block, k, scale


@pytest.mark.parametrize("impl", ["numpy", "xla", "interpret"])
def test_code_nearest_at_rounding_midpoint(impl):
    block, k, scale = _midpoint_block()
    if impl == "numpy":
        q, s = quantize_np(block)
    else:
        q, s = quantize(block, impl=impl)
    assert np.asarray(s)[0, 0] == scale
    assert int(np.asarray(q)[0, 1]) == k + 1
    assert abs(np.float64(block[0, 1]) - (k + 1) * np.float64(scale)) \
        <= scale / 2


def test_delta_identical_is_zero():
    x = RNG.standard_normal(5000).astype(np.float32)
    q, s = quantize(x, impl="interpret")
    d, s2, q2 = quantize_delta(x, q, impl="interpret")
    assert np.all(np.asarray(d) == 0)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))


def test_delta_roundtrip():
    x0 = RNG.standard_normal(3000).astype(np.float32)
    x1 = x0 + RNG.standard_normal(3000).astype(np.float32) * 0.01
    q0, _ = quantize(x0, impl="xla")
    d, s1, q1 = quantize_delta(x1, q0, impl="xla")
    x1r = undelta_dequantize(d, q0, s1, (3000,), jnp.float32, impl="xla")
    q1r = jnp.bitwise_xor(d, q0)
    np.testing.assert_array_equal(np.asarray(q1r), np.asarray(q1))
    assert np.abs(np.asarray(x1r) - x1).max() <= np.abs(x1).max() / 127 * 0.51


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 2000), st.integers(0, 2**31 - 1))
def test_property_roundtrip(n, seed):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) * rng.uniform(0.1, 100)).astype(np.float32)
    q, s = quantize(x, impl="xla")
    xr = np.asarray(dequantize(q, s, (n,), jnp.float32, impl="xla"))
    blocks = np.asarray(_to_blocks(jnp.asarray(x))[0])
    bound = np.abs(blocks).max(axis=1) / 127 * 0.51 + 1e-9
    err = np.abs(xr - x).reshape(-1)
    per_block = np.abs(np.asarray(_to_blocks(jnp.asarray(xr - x))[0]))
    assert np.all(per_block.max(axis=1) <= bound)


def test_zero_block_scale_is_one():
    x = np.zeros(BLOCK, np.float32)
    q, s = quantize(x, impl="interpret")
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(s) == 1.0)
