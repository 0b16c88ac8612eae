"""GQA attention block: train/prefill (flash kernel) + decode (KV cache).

Decode deliberately uses a plain einsum over the cache instead of the flash
kernel: with T=1 the step is HBM-bound on reading the cache, and the einsum
form propagates GSPMD shardings cleanly whether the cache is sharded over
kv-heads (divisible case) or over the sequence axis (kv_seq fallback, used
when kv_heads do not divide the model axis -- softmax statistics and the
PV contraction then reduce over the sharded axis with an all-reduce).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_impl
from repro.kernels.flash_attention import attention as flash_attention
from repro.sharding import constrain
from repro.sharding.rules import active_rules, spec

from .layers import _dense_init, apply_rope

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array                      # (B, Hkv, S, D) -- bf16, or int8 codes
    v: jax.Array
    ks: Optional[jax.Array] = None    # int8 mode: (B, Hkv, S, D/blk) f16
    vs: Optional[jax.Array] = None    # scales (see _q8)


# Scale granularity of the int8 KV cache: one f16 scale per head, per
# position, per `_Q8_SCALE_BLOCK` contiguous head dims.  A single
# per-position scale (the old scheme) lets one outlier dim set the step for
# the whole vector; on the seamless (frames/cross-attention) arch the
# resulting ~1.4e-2 logit noise exceeded near-tie argmax gaps and decode
# diverged.  Sub-head blocks cut the error ~2-3x; f16 scales keep the
# quantized cache well under half the f32 cache (scale error ~2^-11 is
# negligible next to int8 rounding at 1/254).
_Q8_SCALE_BLOCK = 4


def _q8_block(head_dim: int) -> int:
    """Scale-block size for a head dim (whole head when not divisible)."""
    return _Q8_SCALE_BLOCK if head_dim % _Q8_SCALE_BLOCK == 0 else head_dim


def _q8(x):
    """Blockwise int8 quantization along the head dim.

    x: (..., D) -> (codes int8 (..., D), scales f16 (..., D/blk)),
    symmetric absmax scaling per block."""
    d = x.shape[-1]
    blk = _q8_block(d)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // blk, blk))
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return codes.reshape(x.shape), scale[..., 0].astype(jnp.float16)


def _dq(codes, scales):
    """Dequantize _q8 output to f32 (codes (..., D), scales (..., D/blk))."""
    d = codes.shape[-1]
    nb = scales.shape[-1]
    xf = codes.astype(jnp.float32).reshape(codes.shape[:-1] + (nb, d // nb))
    return (xf * scales.astype(jnp.float32)[..., None]).reshape(codes.shape)


def attn_init(key, d_model, num_heads, num_kv_heads, head_dim,
              qkv_bias: bool = False, cross: bool = False):
    """K and V projections are STACKED on a leading axis (one contraction,
    one backward dx all-reduce -- hillclimb H1)."""
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = _dense_init(ks[0], (d_model, num_heads * head_dim),
                                   ("embed", "heads"))
    wkv = jax.random.normal(ks[1], (2, d_model, num_kv_heads * head_dim),
                            jnp.float32) * d_model ** -0.5
    p["wkv"], a["wkv"] = wkv, ("stack", "embed", "kv_heads")
    p["wo"], a["wo"] = _dense_init(ks[3], (num_heads * head_dim, d_model),
                                   ("heads", "embed"))
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), jnp.float32)
        p["bkv"] = jnp.zeros((2, num_kv_heads * head_dim,), jnp.float32)
        a["bq"], a["bkv"] = ("heads",), ("stack", "kv_heads")
    return p, a


def _project_qkv(params, x, xkv, num_heads, num_kv_heads, head_dim):
    b, t, _ = x.shape
    s = xkv.shape[1]
    q = x @ params["wq"].astype(x.dtype)
    kv = jnp.einsum("bsd,kdh->kbsh", xkv, params["wkv"].astype(x.dtype))
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        kv = kv + params["bkv"].astype(x.dtype)[:, None, None, :]
    k, v = kv[0], kv[1]
    q = q.reshape(b, t, num_heads, head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, num_kv_heads, head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, num_kv_heads, head_dim).transpose(0, 2, 1, 3)
    return q, k, v


def sharded_flash_attention(q, k, v, *, causal: bool = True,
                            window: Optional[int] = None,
                            scale: Optional[float] = None,
                            impl: Optional[str] = None):
    """``flash_attention`` under the active mesh.

    XLA does not partition a Mosaic kernel, so with Pallas on a mesh of
    several devices the kernel runs per shard in ``shard_map``, split over
    the batch axes the rules give (heads whole: a GQA group must not be cut
    apart from its kv head).  Without a mesh, or on the XLA path, it is the
    plain call.
    """
    active = active_rules()
    mesh = active[0] if active else None
    if mesh is None or mesh.size == 1 \
            or resolve_impl(impl) not in ("pallas", "interpret"):
        return flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, impl=impl)
    batch = spec(("batch",), active[1], mesh, q.shape[:1])
    part = jax.sharding.PartitionSpec(*batch, None, None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        window=window, scale=scale,
                                        impl=impl),
        mesh=mesh, in_specs=(part, part, part), out_specs=part,
        # pallas_call's out_shape states no varying mesh axes to check
        check_vma=False)(q, k, v)


def attn_apply(params, x, *, num_heads, num_kv_heads, head_dim,
               positions=None, causal: bool = True,
               window: Optional[int] = None, rope_theta: float = 10000.0,
               use_rope: bool = True, xkv=None, impl: Optional[str] = None,
               return_cache: bool = False):
    """Full-sequence attention (train / prefill / encoder / cross).

    ``xkv`` (for cross-attention) defaults to ``x`` (self-attention).
    Returns ``out`` or ``(out, KVCache)`` when ``return_cache``.
    """
    b, t, _ = x.shape
    self_attn = xkv is None
    xkv = x if xkv is None else xkv
    q, k, v = _project_qkv(params, x, xkv, num_heads, num_kv_heads, head_dim)
    if use_rope and self_attn:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t), (b, t))
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    q = constrain(q, "batch", "act_heads", "seq", None)
    k = constrain(k, "batch", "act_kv_heads", "kv_seq", None)
    v = constrain(v, "batch", "act_kv_heads", "kv_seq", None)
    o = sharded_flash_attention(q, k, v, causal=causal and self_attn,
                                window=window, impl=impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, num_heads * head_dim)
    out = o @ params["wo"].astype(x.dtype)
    out = constrain(out, "batch", "seq", "act_embed")
    if return_cache:
        return out, KVCache(k=k, v=v)
    return out


def cross_kv(params, enc_out, num_kv_heads, head_dim, dtype):
    """Project encoder outputs into a static cross-attention KV cache."""
    b, s, _ = enc_out.shape
    kv = jnp.einsum("bsd,kdh->kbsh", enc_out,
                    params["wkv"].astype(enc_out.dtype))
    if "bkv" in params:
        kv = kv + params["bkv"].astype(enc_out.dtype)[:, None, None, :]
    k = kv[0].reshape(b, s, num_kv_heads, head_dim).transpose(0, 2, 1, 3)
    v = kv[1].reshape(b, s, num_kv_heads, head_dim).transpose(0, 2, 1, 3)
    return KVCache(k=k.astype(dtype), v=v.astype(dtype))


# --------------------------------------------------------------------------
# decode path
# --------------------------------------------------------------------------
def init_kv_cache(batch, num_kv_heads, max_len, head_dim, dtype,
                  quant: bool = False):
    if quant:
        nb = head_dim // _q8_block(head_dim)
        z = jnp.zeros((batch, num_kv_heads, max_len, head_dim), jnp.int8)
        s = jnp.ones((batch, num_kv_heads, max_len, nb), jnp.float16)
        return KVCache(k=z, v=z, ks=s, vs=s)
    z = jnp.zeros((batch, num_kv_heads, max_len, head_dim), dtype)
    return KVCache(k=z, v=z)


def cache_axes(quant: bool = False):
    ax = ("batch", "act_kv_heads", "kv_seq", None)
    if quant:
        return KVCache(k=ax, v=ax, ks=ax, vs=ax)
    return KVCache(k=ax, v=ax)


def attn_decode(params, x, cache: KVCache, idx, *, num_heads, num_kv_heads,
                head_dim, rope_theta: float = 10000.0, use_rope: bool = True,
                window: Optional[int] = None, cross: bool = False,
                scale: Optional[float] = None):
    """One-token decode. x: (B, 1, d_model); idx: scalar current position.

    For sliding-window layers the cache is a ring buffer of size
    ``window`` -- keys are RoPE'd with absolute positions at insert time, so
    overwriting old slots needs no re-rotation.  ``cross=True`` attends over
    a static (prefilled) cache without inserting.
    """
    b = x.shape[0]
    s = cache.k.shape[2]
    if scale is None:
        scale = head_dim ** -0.5
    q = x @ params["wq"].astype(x.dtype)
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
    q = q.reshape(b, 1, num_heads, head_dim).transpose(0, 2, 1, 3)
    pos = jnp.broadcast_to(idx[None], (b, 1)).astype(jnp.int32)
    if use_rope:
        q = apply_rope(q, pos, rope_theta)

    if not cross:
        kv_new = jnp.einsum("bsd,kdh->kbsh", x,
                            params["wkv"].astype(x.dtype))
        if "bkv" in params:
            kv_new = kv_new + params["bkv"].astype(x.dtype)[:, None, None, :]
        k_new = kv_new[0].reshape(b, 1, num_kv_heads, head_dim) \
            .transpose(0, 2, 1, 3)
        v_new = kv_new[1].reshape(b, 1, num_kv_heads, head_dim) \
            .transpose(0, 2, 1, 3)
        if use_rope:
            k_new = apply_rope(k_new, pos, rope_theta)
        slot = idx % s if window is not None else idx
        if cache.ks is not None:                 # int8 KV mode
            kq, ksc = _q8(k_new)
            vq, vsc = _q8(v_new)
            cache = KVCache(
                k=jax.lax.dynamic_update_slice(cache.k, kq, (0, 0, slot, 0)),
                v=jax.lax.dynamic_update_slice(cache.v, vq, (0, 0, slot, 0)),
                ks=jax.lax.dynamic_update_slice(cache.ks, ksc,
                                                (0, 0, slot, 0)),
                vs=jax.lax.dynamic_update_slice(cache.vs, vsc,
                                                (0, 0, slot, 0)))
        else:
            k_buf = jax.lax.dynamic_update_slice(
                cache.k, k_new.astype(cache.k.dtype), (0, 0, slot, 0))
            v_buf = jax.lax.dynamic_update_slice(
                cache.v, v_new.astype(cache.v.dtype), (0, 0, slot, 0))
            cache = KVCache(k=k_buf, v=v_buf, ks=cache.ks, vs=cache.vs)

    # einsum attention over the cache (GQA via head grouping).  int8 caches
    # dequantize blockwise first -- the cache was being materialized to f32
    # for the contraction anyway, and per-sub-block scales cannot be
    # factored out of the dot product the way a whole-vector scale could
    g = num_heads // num_kv_heads
    qg = q.reshape(b, num_kv_heads, g, head_dim)
    kf = _dq(cache.k, cache.ks) if cache.ks is not None \
        else cache.k.astype(jnp.float32)
    vf = _dq(cache.v, cache.vs) if cache.vs is not None \
        else cache.v.astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32) * scale, kf)
    kpos = jnp.arange(s)
    if cross:
        valid = kpos[None, None, None, :] >= 0   # whole prefilled cache
    elif window is not None:
        written = jnp.minimum(idx + 1, s)
        valid = kpos[None, None, None, :] < written
    else:
        valid = kpos[None, None, None, :] <= idx
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, vf)
    o = o.reshape(b, 1, num_heads * head_dim).astype(x.dtype)
    out = o @ params["wo"].astype(x.dtype)
    return constrain(out, "batch", None, "act_embed"), cache
