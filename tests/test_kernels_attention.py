"""Flash-attention kernel: shape/dtype/mask sweeps + gradients vs oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention, attention_ref

RNG = np.random.default_rng(7)


def _mk(b, hq, hkv, t, s, d, dtype=np.float32):
    q = RNG.standard_normal((b, hq, t, d)).astype(dtype)
    k = RNG.standard_normal((b, hkv, s, d)).astype(dtype)
    v = RNG.standard_normal((b, hkv, s, d)).astype(dtype)
    return q, k, v


SWEEP = [
    # b, hq, hkv, t, s, d, causal, window
    (2, 4, 2, 128, 128, 64, True, None),
    (1, 8, 1, 100, 100, 32, True, None),      # MQA, unaligned T
    (1, 4, 4, 64, 64, 128, False, None),      # MHA, bidirectional
    (2, 4, 2, 96, 96, 32, True, 32),          # sliding window
    (1, 2, 1, 1, 160, 64, True, None),        # decode-like (T=1)
    (1, 2, 2, 72, 200, 32, True, None),       # cross-length causal
]


@pytest.mark.parametrize("impl", ["interpret", "xla"])
@pytest.mark.parametrize("case", SWEEP)
def test_forward_matches_ref(impl, case):
    b, hq, hkv, t, s, d, causal, window = case
    q, k, v = _mk(b, hq, hkv, t, s, d)
    ref = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal, window=window)
    out = attention(q, k, v, causal=causal, window=window, impl=impl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_bf16(impl):
    q, k, v = _mk(2, 4, 2, 64, 64, 64, np.float32)
    qb, kb, vb = (jnp.asarray(x).astype(jnp.bfloat16) for x in (q, k, v))
    ref = attention_ref(qb, kb, vb, causal=True)
    out = attention(qb, kb, vb, causal=True, impl=impl)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("impl", ["interpret", "xla"])
@pytest.mark.parametrize("case", [SWEEP[0], SWEEP[3], SWEEP[2]])
def test_grads_match_ref(impl, case):
    b, hq, hkv, t, s, d, causal, window = case
    q, k, v = _mk(b, hq, hkv, t, s, d)

    def mk_loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fn(q, k, v)))

    g_ref = jax.grad(mk_loss(lambda q, k, v: attention_ref(
        q, k, v, causal=causal, window=window)), argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g = jax.grad(mk_loss(lambda q, k, v: attention(
        q, k, v, causal=causal, window=window, impl=impl)),
        argnums=(0, 1, 2))(q, k, v)
    for gi, gr, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gi), np.asarray(gr),
                                   atol=5e-4, err_msg=f"d{name}")


def test_fully_masked_rows_are_zero():
    # window smaller than the gap: first rows attend only to themselves
    q, k, v = _mk(1, 2, 2, 8, 8, 16)
    out = attention(q, k, v, causal=True, window=1, impl="xla")
    ref = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=True, window=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# latent attention (MLA) attends with 192-wide queries and keys over
# 128-wide values; the same shapes at a small size, and an unaligned pair
DV_CASES = [
    # b, hq, hkv, t, s, d_qk, d_v, causal
    (1, 4, 4, 128, 128, 48, 32, True),
    (2, 2, 1, 100, 100, 24, 40, True),        # GQA, unaligned T
    (1, 2, 2, 64, 64, 64, 16, False),
]


@pytest.mark.parametrize("case", DV_CASES)
def test_value_dim_differs_forward(case):
    """The Pallas kernel (interpret mode) and the XLA path agree with each
    other and with the oracle when the value head is narrower or wider
    than the query/key head (f32, so 3e-5 is a few ulps of the softmax
    sums)."""
    b, hq, hkv, t, s, d, dv, causal = case
    q, k, _ = _mk(b, hq, hkv, t, s, d)
    v = RNG.standard_normal((b, hkv, s, dv)).astype(np.float32)
    ref = attention_ref(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal)
    got = {impl: attention(q, k, v, causal=causal, impl=impl)
           for impl in ("interpret", "xla")}
    assert got["interpret"].shape == (b, hq, t, dv)
    np.testing.assert_allclose(np.asarray(got["interpret"]),
                               np.asarray(got["xla"]), atol=3e-5)
    np.testing.assert_allclose(np.asarray(got["xla"]), np.asarray(ref),
                               atol=3e-5)


@pytest.mark.parametrize("case", DV_CASES)
def test_value_dim_differs_grads(case):
    """Gradients through the kernel's forward (interpret mode) and the XLA
    backward, against the XLA forward's and the oracle's (5e-4 as for equal
    dims: the backward recomputes the softmax from the saved log-sum-exp)."""
    b, hq, hkv, t, s, d, dv, causal = case
    q, k, _ = _mk(b, hq, hkv, t, s, d)
    v = RNG.standard_normal((b, hkv, s, dv)).astype(np.float32)

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v))),
                        argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                           jnp.asarray(v))

    g_ref = grads(lambda q, k, v: attention_ref(q, k, v, causal=causal))
    for impl in ("interpret", "xla"):
        g = grads(lambda q, k, v: attention(q, k, v, causal=causal,
                                            impl=impl))
        for gi, gr, name in zip(g, g_ref, "qkv"):
            assert gi.shape == gr.shape
            np.testing.assert_allclose(np.asarray(gi), np.asarray(gr),
                                       atol=5e-4, err_msg=f"{impl} d{name}")
