"""deepseek-v2-lite at a tiny size against the plain reference.

The reference is the benchmark's own family module
(``chipbench/models/mla_moe_decoder.py``), so the tier-1 tests and the
chip's ``correct`` check hold the program to one reference.  Weights come
from the reference's seeded init and are handed to the program by leaf
name.  Everything runs in float32 on the CPU, with the flash attention's
XLA path.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.models import mla_moe_decoder as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core import ICheckCluster  # noqa: E402
from repro.core.snapshot import leaf_names  # noqa: E402
from repro.models import init_params, loss_fn  # noqa: E402
from repro.models import mla as mla_lib  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models.layers import ffn_apply  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.train import ElasticTrainer  # noqa: E402

PROGRAM = get_config("deepseek-v2-lite", tiny=True)
# the reference's configuration keys for the same tiny model: the held
# share and the router's width set per test
CFG = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 3, "vocab_size": 256,
    "rope_theta": 10000.0, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "first_k_dense_replace": 1,
    "router_experts": 16, "n_routed_experts": 16, "expert_offset": 0,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "norm_topk_prob": False, "aux_loss_alpha": 0.001,
    "rope_scaling": {"type": "yarn", "factor": 40.0,
                     "original_max_position_embeddings": 64,
                     "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "compute_dtype": "float32", "seq_len": 32, "global_batch": 2,
}
# f32 against f32 at ``highest``: the program's blockwise attention and
# fused contractions differ from the reference in summation order only,
# a few ulps of the O(1) activations
ATOL = 2e-5


def program_config(cfg):
    return dataclasses.replace(
        PROGRAM, **{f: cfg[k] for f, k in ref.PROGRAM_FIELDS.items()})


def weights(cfg, seed=0):
    return jax.jit(lambda kd: ref.init_params(cfg, kd))(ref.key_data(seed))


def to_program(pcfg, flat):
    """The reference's flat weights as the program's parameter tree."""
    tree, _ = init_params(pcfg, jax.random.key(0))
    _, tdef = jax.tree_util.tree_flatten(tree)
    names = ["params/" + n for n in leaf_names(tree)]
    assert sorted(names) == sorted(flat)
    return jax.tree_util.tree_unflatten(tdef, [flat[n] for n in names])


def layer(flat, pre, i=None):
    out = {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}
    return out if i is None else {k: v[i] for k, v in out.items()}


def tokens(cfg, seed=1):
    return jnp.asarray(ref.SeededTokens(cfg, seed).next_batch(
        cfg["global_batch"])["tokens"])


def test_mla_matches_reference():
    """Latent attention with YaRN frequencies, the shared rope key and the
    mscale**2 softmax scale, one MoE layer's weights."""
    cfg = dict(CFG)
    pcfg = program_config(cfg)
    flat = weights(cfg)
    x = jax.random.normal(jax.random.key(2), (2, 32, 64), jnp.float32)
    lp = layer(flat, "params/stack/b0/", 1)
    want = ref.mla(cfg, x, lp)
    p = to_program(pcfg, flat)["stack"]["b0"]["attn"]
    p = jax.tree.map(lambda w: w[1], p)
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    got = mla_lib.mla_apply(pcfg, p, x, positions=pos, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    # the scale: mscale = 0.1 * 0.707 * ln 40 + 1, squared, over sqrt(24)
    m = 0.1 * 0.707 * np.log(40.0) + 1
    assert mla_lib.softmax_scale(pcfg) == pytest.approx(m * m / np.sqrt(24))
    assert ref.softmax_scale(cfg) == pytest.approx(m * m / np.sqrt(24))


def _moe_setup(e_all=64, k=6):
    cfg = dict(CFG, router_experts=e_all, n_routed_experts=e_all,
               num_experts_per_tok=k, hidden_size=32,
               moe_intermediate_size=16)
    flat = weights(cfg, seed=3)
    lp = layer(flat, "params/stack/b0/", 0)
    x = jax.random.normal(jax.random.key(4), (2, 16, 32), jnp.float32)
    return cfg, lp, x


def _program_share(lp, x, off, held, e_all, k):
    p = {"router": lp["moe/router"],
         "w_gu": lp["moe/w_gu"][:, off:off + held],
         "w_down": lp["moe/w_down"][off:off + held],
         "shared": {"w_gu": lp["moe/shared/w_gu"],
                    "w_down": lp["moe/shared/w_down"]}}
    return moe_lib.moe_apply(p, x, num_experts=e_all, experts_per_token=k,
                             expert_offset=off, norm_topk_prob=False,
                             aux_coef=0.001)


def test_expert_shares_add_up_to_the_whole_layer():
    """The 8 chips' shares of an 8-way expert-parallel layer (offsets 0, 8,
    ..., 56 of 64), their routed parts summed and the shared expert that
    every chip computes counted once, are the uncut reference layer; every
    (token, slot) pair is computed by exactly one share."""
    cfg, lp, x = _moe_setup()
    want, want_aux = ref.moe(cfg, x, lp)
    shared = ffn_apply({"w_gu": lp["moe/shared/w_gu"],
                        "w_down": lp["moe/shared/w_down"]}, x)
    total, rows = shared, 0
    for off in range(0, 64, 8):
        y, aux, r = _program_share(lp, x, off, 8, 64, 6)
        total = total + (y - shared)
        rows = rows + int(jnp.sum(r))
        # the balance loss is the router's, the same on every chip
        np.testing.assert_allclose(float(aux), float(want_aux), rtol=1e-5)
    assert rows == 2 * 16 * 6
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=ATOL)


def test_one_share_matches_the_reference_share():
    """A chip that holds experts 8..15 against the reference given the
    same share."""
    cfg, lp, x = _moe_setup()
    share = dict(lp, **{"moe/w_gu": lp["moe/w_gu"][:, 8:16],
                        "moe/w_down": lp["moe/w_down"][8:16]})
    want, _ = ref.moe(dict(cfg, n_routed_experts=8, expert_offset=8), x,
                      share)
    got, _, rows = _program_share(lp, x, 8, 8, 64, 6)
    assert rows.shape == (8,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_dropless_under_skewed_routing():
    """Every token routed to the same 6 held experts: with no capacity,
    each expert computes all 32 tokens' rows and none is dropped."""
    cfg, lp, x = _moe_setup()
    x = jnp.abs(x) + 0.1
    hot = jnp.zeros((32, 64)).at[:, :6].set(5.0).at[:, 6:].set(-5.0)
    lp = dict(lp, **{"moe/router": hot})
    got, _, rows = _program_share(lp, x, 0, 8, 64, 6)
    np.testing.assert_array_equal(np.asarray(rows), [32] * 6 + [0, 0])
    share = dict(lp, **{"moe/w_gu": lp["moe/w_gu"][:, :8],
                        "moe/w_down": lp["moe/w_down"][:8]})
    want, _ = ref.moe(dict(cfg, n_routed_experts=8), x, share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_loss_and_gradients_match_reference():
    """The whole tiny model (a dense lead layer, two MoE layers holding 8 of
    16 experts from offset 4): loss with the balance loss, and every
    leaf's gradient (relative to its norm: 1e-4 covers f32 summation
    order through attention, the router's softmax and the head)."""
    cfg = dict(CFG, n_routed_experts=8, expert_offset=4)
    pcfg = program_config(cfg)
    flat = weights(cfg, seed=5)
    toks = tokens(cfg)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss(cfg, p, toks, chunk=8))(flat)
    params = to_program(pcfg, flat)
    batch = {"tokens": toks, "labels": toks}
    (got, metrics), g_got = jax.value_and_grad(
        lambda p: loss_fn(pcfg, p, batch, impl="xla"), has_aux=True)(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert metrics["moe_rows"].shape == (2, 8)
    g_flat = dict(zip(["params/" + n for n in leaf_names(g_got)],
                      jax.tree.leaves(g_got)))
    for name, gw in g_want.items():
        gp = g_flat[name]
        scale = float(jnp.linalg.norm(gw)) or 1.0
        err = float(jnp.linalg.norm(gp - gw)) / scale
        assert err < 1e-4, (name, err)


def test_raw_save_kill_resume_is_bit_identical():
    """Through ElasticTrainer: 3 steps, a raw commit, a kill (no finalize),
    a new trainer restores and runs 3 steps; its losses and state equal an
    uninterrupted trainer's, bit for bit."""
    shape = ShapeConfig("t", "train", 32, 2)
    opt = AdamWConfig(lr=1e-3)
    kw = dict(seed=3, opt_cfg=opt, commit_every=0, probe_every=0,
              total_steps=10)
    with ICheckCluster(n_icheck_nodes=2) as cluster:
        whole = ElasticTrainer(PROGRAM, shape, cluster, app_id="whole", **kw)
        whole.run(6)
        want = [m["loss"] for m in whole.metrics_log[3:]]
        want_state = [np.asarray(x) for x in jax.tree.leaves(whole.state)]
        rows = whole.moe_rows(6)
        assert rows.shape == (6, 2, 16)
        # every (token, slot) pair of every layer is computed
        assert (rows.sum(axis=2) == 2 * 32 * 3).all()
        whole.finalize()

        t1 = ElasticTrainer(PROGRAM, shape, cluster, app_id="app", **kw)
        t1.run(3)
        t1.commit(blocking=True)
        t2 = ElasticTrainer(PROGRAM, shape, cluster, app_id="app", **kw)
        assert t2.restarted and int(t2.state.step) == 3
        t2.run(3)
        got = [m["loss"] for m in t2.metrics_log]
        got_state = [np.asarray(x) for x in jax.tree.leaves(t2.state)]
        t2.finalize()
    assert got == want
    for a, b in zip(got_state, want_state):
        np.testing.assert_array_equal(a, b)
