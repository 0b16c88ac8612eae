"""Core building blocks: RMSNorm, dense projections, RoPE, gated FFNs,
embeddings.  Pure-functional: every ``*_init`` returns ``(params, axes)``
where ``axes`` mirrors ``params`` with tuples of *logical* axis names
(resolved to PartitionSpecs by ``repro.sharding.rules``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import constrain


def _dense_init(key, shape, axes, scale=None, dtype=jnp.float32):
    fan_in = shape[0]
    if scale is None:
        scale = fan_in ** -0.5
    w = jax.random.normal(key, shape, dtype) * scale
    return w, axes


# --------------------------------------------------------------------------
# RMSNorm
#
# custom_vjp with *compute-dtype cotangent boundaries* (hillclimb H2): the
# statistics run in f32 registers, but the saved residual is the bf16 x and
# dx leaves in bf16 -- without this, XLA's excess-precision pass promotes
# the loop-carried residual-stream cotangents (and the TP all-reduces that
# move them) to f32, doubling HBM + ICI traffic.
# --------------------------------------------------------------------------
def rmsnorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": ("embed",)}


@jax.custom_vjp
def _rmsnorm_core(x, scale):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + 1e-6) * scale
    return y.astype(x.dtype)


def _rmsnorm_fwd(x, scale):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rsig = jax.lax.rsqrt(var + 1e-6)
    y = (xf * rsig * scale).astype(x.dtype)
    return y, (x, rsig, scale)


def _rmsnorm_bwd(res, dy):
    x, rsig, scale = res
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32) * scale
    inner = jnp.sum(dyf * xf, axis=-1, keepdims=True) / d
    dx = rsig * (dyf - xf * (rsig * rsig) * inner)
    dscale = jnp.sum((dy.astype(jnp.float32)
                      * xf * rsig).reshape(-1, d), axis=0)
    return dx.astype(x.dtype), dscale


_rmsnorm_core.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(params, x, eps: float = 1e-6):
    return _rmsnorm_core(x, params["scale"])


def groupnorm_heads(x, scale, bias, eps: float = 64e-5):
    """Per-head group norm used by RWKV-6 on the wkv output.

    x: (B, T, H, D) normalized over D per head; scale/bias: (H, D).
    """
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_inv_freq(dim: int, theta: float, factor: float = 1.0,
                  original_max_pos: int = 0, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """Inverse frequencies of the ``dim // 2`` rotated pairs.

    With ``factor`` > 1, YaRN's (arXiv:2309.00071, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): pairs that turn fewer than
    ``beta_slow`` times over ``original_max_pos`` positions are
    interpolated (frequency / factor), those that turn more than
    ``beta_fast`` times are kept, with a linear ramp between."""
    half = dim // 2
    extra = theta ** (-np.arange(half, dtype=np.float64) / half)
    if factor <= 1:
        return extra.astype(np.float32)

    def pair(rotations):   # the pair that turns ``rotations`` times
        return dim * math.log(original_max_pos / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(half) - low) / (high - low), 0, 1)
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x, positions, theta: float = 10000.0, inv_freq=None):
    """x: (B, H, T, D); positions: (B, T) absolute positions.

    Rotate-half pairs (i, i + D/2) at ``inv_freq`` (plain RoPE at ``theta``
    when None)."""
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freq = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[:, None, :, None].astype(jnp.float32) * freq  # (B,1,T,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Gated FFN (SwiGLU / GeGLU)
#
# gate and up projections are STACKED on a leading axis and applied with one
# contraction: the backward dx then sums the two branches *locally inside
# the dot* before GSPMD's partial-sum all-reduce -- one (B,T,D) all-reduce
# per layer instead of two (perf hillclimb H1, EXPERIMENTS.md SSPerf).
# --------------------------------------------------------------------------
def ffn_init(key, d_model, d_ff):
    k1, k3 = jax.random.split(key, 2)
    p, a = {}, {}
    w = jax.random.normal(k1, (2, d_model, d_ff), jnp.float32) \
        * d_model ** -0.5
    p["w_gu"], a["w_gu"] = w, ("stack", "embed", "ff")
    p["w_down"], a["w_down"] = _dense_init(k3, (d_ff, d_model), ("ff", "embed"))
    return p, a


def ffn_apply(params, x, kind: str = "swiglu"):
    act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
    wgu = params["w_gu"].astype(x.dtype)
    wd = params["w_down"].astype(x.dtype)
    gu = jnp.einsum("btd,kdf->kbtf", x, wgu)
    h = act(gu[0]) * gu[1]
    h = constrain(h, "batch", "seq", "act_ff")
    return h @ wd


# --------------------------------------------------------------------------
# Embedding / LM head
# --------------------------------------------------------------------------
def embed_init(key, vocab, d_model):
    w = jax.random.normal(key, (vocab, d_model), jnp.float32)
    return {"table": w}, {"table": ("vocab", "embed")}


def embed_apply(params, tokens):
    out = jnp.take(params["table"], tokens, axis=0)
    return constrain(out, "batch", "seq", "act_embed")


def lm_head_init(key, d_model, vocab):
    p, a = {}, {}
    p["w"], a["w"] = _dense_init(key, (d_model, vocab), ("embed", "vocab"))
    return p, a


def lm_head_apply(params, x, valid_vocab: int = 0):
    """valid_vocab > 0: the head is padded; mask the tail to -inf so the
    padded logits are inert in softmax/argmax (no slice -> no resharding)."""
    logits = x @ params["w"].astype(x.dtype)
    vp = logits.shape[-1]
    if valid_vocab and valid_vocab < vp:
        ok = jax.lax.broadcasted_iota(jnp.int32, (vp,), 0) < valid_vocab
        logits = jnp.where(ok, logits, jnp.asarray(-1e30, logits.dtype))
    return constrain(logits, "batch", "seq", "act_vocab")


# --------------------------------------------------------------------------
# Frontend stubs (assignment: audio frames / vision patches arrive as
# precomputed embeddings via input_specs; the frontend is a projection)
# --------------------------------------------------------------------------
def frontend_init(key, d_in, d_model):
    p, a = {}, {}
    p["proj"], a["proj"] = _dense_init(key, (d_in, d_model), (None, "embed"))
    return p, a


def frontend_apply(params, embeds):
    out = embeds @ params["proj"].astype(embeds.dtype)
    return constrain(out, "batch", "seq", "act_embed")
