"""Per-arch reduced-config smoke tests: one forward/train step on CPU with
shape + NaN assertions, decode consistency, and a short learning run."""
import jax
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import (decode_step, forward, init_cache, init_params,
                          prefill)
from repro.optim import AdamWConfig
from repro.train import make_train_state, make_train_step

B, T = 2, 32


def _batch(cfg, key, t=T):
    k1, k2 = jax.random.split(key)
    batch = {"tokens": jax.random.randint(k1, (B, t), 0, cfg.vocab_size),
             "labels": jax.random.randint(k1, (B, t), 0, cfg.vocab_size)}
    if cfg.frontend == "frames":
        batch["frames"] = jax.random.normal(k2, (B, cfg.num_frames,
                                                 cfg.d_model))
    if cfg.frontend == "patches":
        batch["patches"] = jax.random.normal(k2, (B, cfg.num_patches,
                                                  cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nans(arch):
    cfg = get_config(arch, tiny=True)
    params, axes = init_params(cfg, jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x))
    batch = _batch(cfg, jax.random.key(1))
    logits, aux = jax.jit(lambda p, b: forward(cfg, p, b))(params, batch)
    assert logits.shape == (B, T, cfg.vocab_size)
    assert not np.any(np.isnan(logits))
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_finite(arch):
    cfg = get_config(arch, tiny=True)
    state = make_train_state(cfg, jax.random.key(0))
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3)))
    batch = _batch(cfg, jax.random.key(1))
    state, metrics = step(state, batch)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    for leaf in jax.tree.leaves(state.params):
        assert np.all(np.isfinite(np.asarray(leaf, np.float32)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    """Teacher-forcing equivalence: prefill+decode logits == forward logits."""
    # MoE dispatch is dropless: each token's routing is its own, whether
    # the router sees T tokens (forward) or one (decode)
    cfg = get_config(arch, tiny=True)
    params, _ = init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    logits_all, _ = jax.jit(lambda p, b: forward(cfg, p, b))(params, batch)

    prompt = {k: (v[:, :T // 2] if k == "tokens" else v)
              for k, v in batch.items()}
    cache = init_cache(cfg, B, T + (cfg.num_patches
                                    if cfg.frontend == "patches" else 0))
    lg, cache = jax.jit(lambda p, b, c: prefill(cfg, p, b, c))(
        params, prompt, cache)
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(logits_all[:, T // 2 - 1]),
                               atol=2e-3)
    dec = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
    for i in range(T // 2, min(T // 2 + 3, T)):
        tok = batch["tokens"][:, i:i + 1]
        lg, cache = dec(params, cache, tok)
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits_all[:, i]), atol=2e-3,
                                   err_msg=f"{arch} step {i}")


def test_training_reduces_loss():
    """The synthetic Markov stream is learnable: loss must drop clearly."""
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticLMData

    cfg = get_config("qwen2.5-3b", tiny=True)
    shape = ShapeConfig("t", "train", 64, 8)
    data = SyntheticLMData(cfg, shape, seed=0, order_vocab=cfg.vocab_size)
    state = make_train_state(cfg, jax.random.key(0), AdamWConfig(lr=3e-3))
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=3e-3)),
                   donate_argnums=0)
    losses = []
    for _ in range(40):
        state, m = step(state, data.next_batch())
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses[::8]


def test_microbatched_step_matches_plain():
    cfg = get_config("yi-6b", tiny=True)
    state = make_train_state(cfg, jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    s1, m1 = jax.jit(make_train_step(cfg, AdamWConfig()))(state, batch)
    s2, m2 = jax.jit(make_train_step(cfg, AdamWConfig(), microbatches=2))(
        state, batch)
    # bf16 forward + different reduction order: tolerances, not equality
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=3e-3)
    a = jax.tree.leaves(s1.params)
    b = jax.tree.leaves(s2.params)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=5e-3)
