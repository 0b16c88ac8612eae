"""The comparisons: the device half-scale ratio against the float64 one,
exact fingerprints, and the gaps of norms."""
import numpy as np
import pytest

from chipbench import checks


def test_half_scale_ratio_matches_float64():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = rng.standard_normal(5000).astype(np.float32) * 3
    scale = np.abs(x).max() / 127
    noise = rng.uniform(-0.5, 0.5, x.shape) * scale
    y = (x + noise).astype(np.float32)
    want = checks.half_scale_excess_np(x, y)
    got = checks.half_scale_excess(jnp.asarray(x), jnp.asarray(y))
    assert got == pytest.approx(want, rel=1e-5)
    # a value half a block scale off and then some fails
    y2 = x.copy()
    y2[7] += 0.6 * np.abs(x[:256]).max() / 127
    assert checks.half_scale_excess(jnp.asarray(x), jnp.asarray(y2)) > 1
    # an all-zero block must come back exact
    z = np.zeros(512, np.float32)
    assert checks.half_scale_excess(jnp.asarray(z), jnp.asarray(z)) == 0
    z2 = z.copy()
    z2[3] = 1e-30
    assert checks.half_scale_excess(jnp.asarray(z), jnp.asarray(z2)) > 1


def test_int4_control_is_outside_the_bound():
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(1).standard_normal(4096),
                    jnp.float32)
    assert checks.half_scale_excess(x, checks.int4_roundtrip(x)) > 10


def test_fingerprints_see_one_bit():
    import jax.numpy as jnp

    a = np.arange(1000, dtype=np.float32)
    b = a.copy()
    b.view(np.uint32)[500] ^= 1
    fa = checks.fingerprints({"x": jnp.asarray(a), "n": jnp.int32(3)})
    fb = checks.fingerprints({"x": jnp.asarray(b), "n": jnp.int32(3)})
    assert checks.count_differing(fa, fb) == 1
    swapped = a.copy()
    swapped[[1, 2]] = swapped[[2, 1]]
    fs = checks.fingerprints({"x": jnp.asarray(swapped), "n": jnp.int32(3)})
    assert checks.count_differing(fa, fs) == 1


def test_norm_gap_uses_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert checks.norm_gap({"a": 1.1, "b": 2.0, "c": 1e-9}, ref) \
        == pytest.approx(0.1)                # the median leaf's 1.0
    # a leaf that is nought in the reference moves by round-off alone
    assert checks.norm_gap({"a": 1.0, "b": 2.0, "c": 1e-3}, ref) \
        == pytest.approx(1e-3)
    assert checks.moving_leaves(ref) == ["a", "b"]
    assert checks.norm_gap({"a": float("nan"), "b": 2.0, "c": 0.0}, ref) \
        == float("inf")
    assert checks.loss_gap([1.01, 2.0], [1.0, 2.0]) == pytest.approx(0.01)
