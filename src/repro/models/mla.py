"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), without a
query low-rank projection (DeepSeek-V2-Lite's ``q_lora_rank`` null).

With ``x`` the normed input:

  q = x W_q, per head [q_nope (qk_nope_head_dim), q_pe (qk_rope_head_dim)]
  [c_kv, k_pe] = x W_kva; c_kv = RMSNorm(c_kv)    (kv_lora_rank + rope)
  [k_nope, v] = c_kv W_kvb, per head              (nope + v_head_dim)
  q_pe, k_pe rotated at YaRN frequencies; k_pe is one head shared by all
  q = [q_nope, q_pe], k = [k_nope, k_pe]; causal softmax(q k^T * scale) v
  out = o W_o

The softmax scale is ``mscale**2 / sqrt(nope + rope)``, mscale YaRN's
temperature at ``rope_mscale_all_dim``; cos and sin are not scaled, as
YaRN's ratio mscale / mscale_all_dim is 1 where the two are equal, as in
DeepSeek-V2-Lite's config.  RoPE rotates rotate-half pairs
(i, i + rope/2) where the published model rotates interleaved ones: a fixed
permutation of the rope columns of W_q and W_kva, so equivalent under
random weights.  The flash kernel runs with the 192-wide q and k over the
128-wide v; decode keeps the per-head k and v in the cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding import constrain

from .attention import NEG_INF, KVCache, sharded_flash_attention
from .layers import (_dense_init, apply_rope, rmsnorm, rope_inv_freq,
                     yarn_mscale)


def dims(cfg: ModelConfig):
    """(heads, nope, rope, value) head widths."""
    return (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim)


def softmax_scale(cfg: ModelConfig) -> float:
    _, nope, rope, _ = dims(cfg)
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return m * m * (nope + rope) ** -0.5


def mla_init(key, cfg: ModelConfig):
    h, nope, rope, dv = dims(cfg)
    d, r = cfg.d_model, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = _dense_init(ks[0], (d, h * (nope + rope)),
                                   ("embed", "heads"))
    p["wkv_a"], a["wkv_a"] = _dense_init(ks[1], (d, r + rope),
                                         ("embed", None))
    p["kv_norm"] = {"scale": jnp.ones((r,), jnp.float32)}
    a["kv_norm"] = {"scale": ("kv_lora",)}
    p["wkv_b"], a["wkv_b"] = _dense_init(ks[2], (r, h * (nope + dv)),
                                         ("kv_lora", "heads"))
    p["wo"], a["wo"] = _dense_init(ks[3], (h * dv, d), ("heads", "embed"))
    return p, a


def _qkv(cfg: ModelConfig, p, x, positions):
    """x: (B, T, D) -> q, k: (B, H, T, nope + rope), v: (B, H, T, dv)."""
    h, nope, rope, dv = dims(cfg)
    r = cfg.kv_lora_rank
    b, t, _ = x.shape
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, t, h, nope + rope)
    q = q.transpose(0, 2, 1, 3)
    ckv = x @ p["wkv_a"].astype(x.dtype)
    c, k_pe = ckv[..., :r], ckv[..., r:]
    c = rmsnorm(p["kv_norm"], c)
    kv = (c @ p["wkv_b"].astype(x.dtype)).reshape(b, t, h, nope + dv)
    kv = kv.transpose(0, 2, 1, 3)
    inv = rope_inv_freq(rope, cfg.rope_theta, cfg.rope_factor,
                        cfg.rope_original_max_pos)
    q_pe = apply_rope(q[..., nope:], positions, inv_freq=inv)
    k_pe = apply_rope(k_pe[:, None], positions, inv_freq=inv)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, h, t, rope))], axis=-1)
    return q, k, kv[..., nope:]


def mla_apply(cfg: ModelConfig, p, x, *, positions, impl=None,
              return_cache: bool = False):
    """Causal latent attention over the whole sequence (train / prefill).
    Returns ``out`` or ``(out, KVCache)``."""
    h, _, _, dv = dims(cfg)
    b, t, _ = x.shape
    with jax.named_scope("mla"):
        q, k, v = _qkv(cfg, p, x, positions)
        q = constrain(q, "batch", "act_heads", "seq", None)
        k = constrain(k, "batch", "act_heads", "kv_seq", None)
        v = constrain(v, "batch", "act_heads", "kv_seq", None)
        o = sharded_flash_attention(q, k, v, causal=True,
                                    scale=softmax_scale(cfg), impl=impl)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * dv)
        out = constrain(o @ p["wo"].astype(x.dtype),
                        "batch", "seq", "act_embed")
    if return_cache:
        return out, KVCache(k=k, v=v)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> KVCache:
    h, nope, rope, dv = dims(cfg)
    return KVCache(k=jnp.zeros((batch, h, max_len, nope + rope), dtype),
                   v=jnp.zeros((batch, h, max_len, dv), dtype))


def mla_decode(cfg: ModelConfig, p, x, cache: KVCache, idx):
    """One-token decode. x: (B, 1, D); idx: the token's position."""
    h, _, _, dv = dims(cfg)
    b = x.shape[0]
    s = cache.k.shape[2]
    pos = jnp.broadcast_to(idx[None], (b, 1)).astype(jnp.int32)
    q, k, v = _qkv(cfg, p, x, pos)
    cache = KVCache(
        k=jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype),
                                       (0, 0, idx, 0)),
        v=jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype),
                                       (0, 0, idx, 0)))
    scores = jnp.einsum("bhqd,bhsd->bhqs",
                        q.astype(jnp.float32) * softmax_scale(cfg),
                        cache.k.astype(jnp.float32))
    scores = jnp.where(jnp.arange(s) <= idx, scores, NEG_INF)
    o = jnp.einsum("bhqs,bhsd->bhqd", jax.nn.softmax(scores, axis=-1),
                   cache.v.astype(jnp.float32))
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dv).astype(x.dtype)
    out = o @ p["wo"].astype(x.dtype)
    return constrain(out, "batch", None, "act_embed"), cache
