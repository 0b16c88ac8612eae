"""RWKV-6 (Finch) block: data-dependent token-shift time-mix over the
chunked Pallas recurrence kernel + squared-ReLU channel-mix.

State carried for decode: per block,
  ``shift_tm`` / ``shift_cm``: (B, d_model) -- previous token's activations
  ``wkv``: (B, H, Dh, Dh) f32 -- the linear-attention state.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6 import rwkv6 as rwkv6_core
from repro.sharding import constrain

from .layers import _dense_init, groupnorm_heads

LORA_RANK = 32


class RWKVState(NamedTuple):
    shift_tm: jax.Array        # (B, D)
    shift_cm: jax.Array        # (B, D)
    wkv: jax.Array             # (B, H, Dh, Dh) f32


def timemix_init(key, d_model, head_dim):
    h = d_model // head_dim
    ks = jax.random.split(key, 12)
    p, a = {}, {}
    # r/k/v/g projections stacked: one contraction, one bwd dx all-reduce
    p["w_rkvg"] = jax.random.normal(ks[0], (4, d_model, d_model),
                                    jnp.float32) * d_model ** -0.5
    a["w_rkvg"] = ("stack", "embed", "rnn")
    p["wo"], a["wo"] = _dense_init(ks[4], (d_model, d_model), ("rnn", "embed"))
    # data-dependent decay: w = exp(-exp(w0 + (x @ A) @ B))
    p["w0"] = jnp.zeros((d_model,), jnp.float32) - 4.0
    a["w0"] = ("rnn",)
    p["wA"], a["wA"] = _dense_init(ks[5], (d_model, LORA_RANK), ("embed", None))
    p["wB"], a["wB"] = _dense_init(ks[6], (LORA_RANK, d_model), (None, "rnn"),
                                   scale=0.01)
    # token-shift interpolation factors (static mu + data-dependent lora)
    p["mu"] = jnp.full((5, d_model), 0.5, jnp.float32)   # r,k,v,w,g
    a["mu"] = ("stack", "embed")
    p["muA"], a["muA"] = _dense_init(ks[7], (d_model, LORA_RANK), ("embed", None))
    p["muB"], a["muB"] = _dense_init(ks[8], (LORA_RANK, 5 * d_model),
                                     (None, None), scale=0.01)
    p["u"] = jnp.zeros((h, head_dim), jnp.float32)       # bonus
    a["u"] = (None, "rnn")
    p["gn_scale"] = jnp.ones((h, head_dim), jnp.float32)
    p["gn_bias"] = jnp.zeros((h, head_dim), jnp.float32)
    a["gn_scale"] = a["gn_bias"] = (None, "rnn")
    return p, a


def _token_shift(x, last):
    """x: (B, T, D); last: (B, D) previous token (zeros at sequence start)."""
    prev = jnp.concatenate([last[:, None, :].astype(x.dtype), x[:, :-1, :]], 1)
    return prev


def timemix_apply(params, x, state_tm, wkv_state, head_dim, impl=None):
    b, t, d = x.shape
    h = d // head_dim
    prev = _token_shift(x, state_tm)
    delta = prev - x
    # data-dependent interpolation (RWKV-6 "ddlerp")
    lora = jnp.tanh(x @ params["muA"].astype(x.dtype))
    lora = (lora @ params["muB"].astype(x.dtype)).reshape(b, t, 5, d)
    mix = params["mu"].astype(x.dtype)[None, None] + lora
    xr, xk, xv, xw, xg = [x + delta * mix[:, :, i] for i in range(5)]

    xs4 = jnp.stack([xr, xk, xv, xg])                    # (4, B, T, D)
    rkvg = jnp.einsum("nbtd,ndh->nbth", xs4,
                      params["w_rkvg"].astype(x.dtype))
    r, k, v, g = rkvg[0], rkvg[1], rkvg[2], rkvg[3]
    wlog = params["w0"] + jnp.tanh(xw @ params["wA"].astype(x.dtype)) \
        @ params["wB"].astype(x.dtype)
    log_w = -jnp.exp(wlog.astype(jnp.float32))           # (B, T, D) <= 0

    def heads(z):
        return z.reshape(b, t, h, head_dim).transpose(0, 2, 1, 3)

    r_, k_, v_, lw_ = heads(r), heads(k), heads(v), heads(log_w)
    r_ = constrain(r_, "batch", "act_rnn", "seq", None)
    o, wkv_new = rwkv6_core(r_, k_, v_, lw_, params["u"], wkv_state,
                            impl=impl)
    o = o.transpose(0, 2, 1, 3)                          # (B, T, H, Dh)
    o = groupnorm_heads(o, params["gn_scale"], params["gn_bias"])
    o = o.reshape(b, t, d) * jax.nn.silu(g)
    out = o @ params["wo"].astype(x.dtype)
    return constrain(out, "batch", "seq", "act_embed"), x[:, -1, :], wkv_new


def chanmix_init(key, d_model, d_ff):
    k1, k2, k3 = jax.random.split(key, 3)
    p, a = {}, {}
    p["wk"], a["wk"] = _dense_init(k1, (d_model, d_ff), ("embed", "ff"))
    p["wv"], a["wv"] = _dense_init(k2, (d_ff, d_model), ("ff", "embed"))
    p["wr"], a["wr"] = _dense_init(k3, (d_model, d_model), ("embed", "rnn"))
    p["mu"] = jnp.full((2, d_model), 0.5, jnp.float32)   # k, r
    a["mu"] = ("stack", "embed")
    return p, a


def chanmix_apply(params, x, state_cm):
    prev = _token_shift(x, state_cm)
    delta = prev - x
    mu = params["mu"].astype(x.dtype)
    xk = x + delta * mu[0]
    xr = x + delta * mu[1]
    k = jnp.square(jax.nn.relu(xk @ params["wk"].astype(x.dtype)))
    k = constrain(k, "batch", "seq", "act_ff")
    kv = k @ params["wv"].astype(x.dtype)
    out = jax.nn.sigmoid(xr @ params["wr"].astype(x.dtype)) * kv
    return constrain(out, "batch", "seq", "act_embed"), x[:, -1, :]


def init_state(batch, d_model, head_dim, dtype):
    h = d_model // head_dim
    return RWKVState(
        shift_tm=jnp.zeros((batch, d_model), dtype),
        shift_cm=jnp.zeros((batch, d_model), dtype),
        wkv=jnp.zeros((batch, h, head_dim, head_dim), jnp.float32))


def state_axes():
    return RWKVState(shift_tm=("batch", "act_embed"),
                     shift_cm=("batch", "act_embed"),
                     wkv=("batch", "act_rnn", None, None))
