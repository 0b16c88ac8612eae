"""Griffin / RecurrentGemma recurrent block: causal depthwise conv1d +
RG-LRU over the blocked Pallas scan, gated by a GeLU branch.

State carried for decode: ``conv``: (B, conv_width-1, rnn_width) past
inputs; ``h``: (B, rnn_width) f32 recurrent state.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.rglru import rglru as rglru_core
from repro.sharding import constrain

from .layers import _dense_init

RGLRU_C = 8.0  # Griffin's fixed recurrence-sharpness constant


class RGLRUState(NamedTuple):
    conv: jax.Array     # (B, W-1, rnn_width)
    h: jax.Array        # (B, rnn_width) f32


def recurrent_init(key, d_model, rnn_width, conv_width):
    ks = jax.random.split(key, 6)
    p, a = {}, {}
    # in/gate projections stacked (hillclimb H1: one bwd dx all-reduce)
    p["w_ig"] = jax.random.normal(ks[0], (2, d_model, rnn_width),
                                  jnp.float32) * d_model ** -0.5
    a["w_ig"] = ("stack", "embed", "rnn")
    p["w_out"], a["w_out"] = _dense_init(ks[2], (rnn_width, d_model),
                                         ("rnn", "embed"))
    p["conv_w"] = jax.random.normal(ks[3], (conv_width, rnn_width),
                                    jnp.float32) * conv_width ** -0.5
    a["conv_w"] = ("conv", "rnn")
    p["conv_b"] = jnp.zeros((rnn_width,), jnp.float32)
    a["conv_b"] = ("rnn",)
    # recurrence/input gates stacked likewise
    p["w_ai"] = jax.random.normal(ks[4], (2, rnn_width, rnn_width),
                                  jnp.float32) * rnn_width ** -0.5
    a["w_ai"] = ("stack", "rnn", None)
    # Lambda init so that a^c = sigmoid(lam)^c lands in [0.9, 0.999]
    u = jnp.linspace(0.9 ** (1 / RGLRU_C), 0.999 ** (1 / RGLRU_C), rnn_width)
    p["lam"] = jnp.log(u / (1 - u)).astype(jnp.float32)
    a["lam"] = ("rnn",)
    return p, a


def _causal_conv(y, conv_w, conv_b, state):
    """Depthwise causal conv. y: (B, T, N); state: (B, W-1, N) history."""
    w = conv_w.shape[0]
    hist = jnp.concatenate([state.astype(y.dtype), y], axis=1)
    out = jnp.zeros_like(y)
    for i in range(w):
        out = out + hist[:, w - 1 - i: hist.shape[1] - i, :] \
            * conv_w[w - 1 - i].astype(y.dtype)
    new_state = hist[:, -(w - 1):, :] if w > 1 else state
    return out + conv_b.astype(y.dtype), new_state


def recurrent_apply(params, x, state: RGLRUState, impl=None):
    """x: (B, T, d_model) -> (out, new_state)."""
    ig = jnp.einsum("btd,kdn->kbtn", x, params["w_ig"].astype(x.dtype))
    y, gate = ig[0], jax.nn.gelu(ig[1])
    y = constrain(y, "batch", "seq", "act_rnn")
    y, conv_state = _causal_conv(y, params["conv_w"], params["conv_b"],
                                 state.conv)
    yf = y.astype(jnp.float32)
    ai = jnp.einsum("btn,knm->kbtm", yf, params["w_ai"].astype(jnp.float32))
    r = jax.nn.sigmoid(ai[0])
    i = jax.nn.sigmoid(ai[1])
    log_a = -RGLRU_C * jax.nn.softplus(params["lam"]) * r     # (B, T, N) <= 0
    a2 = jnp.exp(2.0 * log_a)
    g = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12)) * i * yf
    h, h_last = rglru_core(log_a, g.astype(x.dtype), state.h, impl=impl)
    h = constrain(h, "batch", "seq", "act_rnn")
    out = (gate * h.astype(x.dtype)) @ params["w_out"].astype(x.dtype)
    out = constrain(out, "batch", "seq", "act_embed")
    return out, RGLRUState(conv=conv_state.astype(state.conv.dtype), h=h_last)


def init_state(batch, rnn_width, conv_width, dtype):
    return RGLRUState(conv=jnp.zeros((batch, conv_width - 1, rnn_width), dtype),
                      h=jnp.zeros((batch, rnn_width), jnp.float32))


def state_axes():
    return RGLRUState(conv=("batch", None, "act_rnn"),
                      h=("batch", "act_rnn"))
