"""Latent-attention MoE decoder family (DeepSeek-V2): weights and data from
a seed, the plain reference, and the work a training step needs.

Nothing here imports the program under test.  The reference is the
architecture written out in float32 ``jax.numpy`` at ``highest`` matmul
precision, for one chip's share of the experts:

- embedding, then ``first_k_dense_replace`` dense layers and the MoE
  layers, each RMSNorm -> MLA -> residual -> RMSNorm -> FFN -> residual,
  a final RMSNorm, an untied head over the vocabulary, next-token cross
  entropy plus the MoE layers' balance loss, and AdamW;
- MLA (no query low-rank projection): ``q = x W_q`` split per head into
  nope and rope parts; ``[c, k_pe] = x W_kva``; ``c = RMSNorm(c)``;
  ``[k_nope, v] = c W_kvb`` per head; q_pe and the one shared k_pe rotated
  at YaRN's frequencies; causal softmax at ``mscale**2 / sqrt(nope +
  rope)``, mscale = 0.1 * mscale_all_dim * ln(factor) + 1; ``W_o``;
- MoE: a router over all ``router_experts`` in f32, softmax, greedy top-k,
  the weights renormalised only with ``norm_topk_prob``; the layer holds
  experts ``[expert_offset, expert_offset + n_routed_experts)`` and adds,
  for every token, ``w * SwiGLU_e(x)`` for each of its top-k experts that
  it holds (every token against every held expert, weighted by zero where
  not routed: no capacity, nothing dropped); the shared SwiGLU of width
  ``n_shared_experts * moe_intermediate_size`` on every token, once;
- the balance loss ``aux_loss_alpha * sum_i f_i P_i`` over all router
  experts, ``f_i = E / (k T) * count_i``, ``P_i`` the mean probability,
  averaged over sequences (DeepSeek's ``seq_aux``).

Departures from the published model: RoPE rotates rotate-half pairs (i, i
+ rope/2), where the published model rotates interleaved ones: a fixed
permutation of the rope columns of W_q and W_kva, so equivalent under
random weights.  What the experts this chip does not hold would add is
left out, as in the program: that is the chip's share.

Its parameter tree uses the leaf names the program's ``TrainState``
flattens to (``params/stack/b0/moe/w_gu`` ...).  Seeded tokens, the key,
the schedule and AdamW are the dense family's, by import.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

from chipbench.models import dense_decoder as dd
from chipbench.models.dense_decoder import (SeededTokens, adamw,  # noqa: F401
                                            key_data)

PAD_VOCAB = dd.PAD_VOCAB
# the program's ModelConfig field each configuration key sets
PROGRAM_FIELDS = {
    "num_layers": "num_hidden_layers", "d_model": "hidden_size",
    "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "num_experts": "router_experts", "experts_held": "n_routed_experts",
    "expert_offset": "expert_offset",
    "experts_per_token": "num_experts_per_tok",
    "moe_d_ff": "moe_intermediate_size", "shared_experts": "n_shared_experts",
    "norm_topk_prob": "norm_topk_prob", "router_aux_coef": "aux_loss_alpha",
    "first_dense_layers": "first_k_dense_replace", "dtype": "compute_dtype",
}


def _dims(cfg: dict):
    """(heads, nope, rope, value, kv rank)."""
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def shapes(cfg: dict) -> Dict[str, tuple]:
    """Leaf name -> shape of the parameters, as the program lays them out
    (MoE layers stacked on a leading axis, gate and up stacked, vocabulary
    rows padded to a multiple of 256)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, nope, rope, dv, r = _dims(cfg)
    lead = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - lead
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    sf = cfg["n_shared_experts"] * f
    vp = -(-cfg["vocab_size"] // PAD_VOCAB) * PAD_VOCAB

    def attn(pre, s):
        return {pre + "attn/wq": s + (d, h * (nope + rope)),
                pre + "attn/wkv_a": s + (d, r + rope),
                pre + "attn/kv_norm/scale": s + (r,),
                pre + "attn/wkv_b": s + (r, h * (nope + dv)),
                pre + "attn/wo": s + (h * dv, d),
                pre + "norm1/scale": s + (d,),
                pre + "norm2/scale": s + (d,)}

    out = {"params/embed/table": (vp, d), "params/final_norm/scale": (d,),
           "params/lm_head/w": (d, vp)}
    for i in range(lead):
        pre = f"params/lead{i}/"
        out.update(attn(pre, ()))
        out[pre + "ffn/w_gu"] = (2, d, ff)
        out[pre + "ffn/w_down"] = (ff, d)
    pre = "params/stack/b0/"
    out.update(attn(pre, (n,)))
    out.update({
        pre + "moe/router": (n, d, cfg["router_experts"]),
        pre + "moe/w_gu": (n, 2, e, d, f),
        pre + "moe/w_down": (n, e, f, d),
        pre + "moe/shared/w_gu": (n, 2, d, sf),
        pre + "moe/shared/w_down": (n, sf, d)})
    return dict(sorted(out.items()))


def state_names(cfg: dict) -> Dict[str, tuple]:
    """Every leaf of the training state: params, AdamW moments, counters."""
    p = shapes(cfg)
    out = dict(p)
    for name, shp in p.items():
        rest = name[len("params/"):]
        out["opt/mu/" + rest] = shp
        out["opt/nu/" + rest] = shp
    out["opt/count"] = ()
    out["step"] = ()
    return out


# --------------------------------------------------------------- weights
def init_params(cfg: dict, kd):
    """Parameters from a key (traced: call under ``jax.jit``): matrices
    normal with std fan_in**-0.5, the embedding std 1, norm scales 1, each
    leaf from its own fold of the key."""
    import jax
    import jax.numpy as jnp

    key = jax.random.wrap_key_data(kd, impl="threefry2x32")
    out = {}
    for i, (name, shp) in enumerate(shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith("/scale"):
            out[name] = jnp.ones(shp, jnp.float32)
        elif name == "params/embed/table":
            out[name] = jax.random.normal(k, shp, jnp.float32)
        else:
            out[name] = jax.random.normal(k, shp, jnp.float32) \
                * shp[-2] ** -0.5
    return out


def init_state(cfg: dict, kd):
    """The whole training state at step 0 (traced)."""
    import jax.numpy as jnp

    params = init_params(cfg, kd)
    out = dict(params)
    for name, x in params.items():
        rest = name[len("params/"):]
        out["opt/mu/" + rest] = jnp.zeros_like(x)
        out["opt/nu/" + rest] = jnp.zeros_like(x)
    out["opt/count"] = jnp.zeros((), jnp.int32)
    out["step"] = jnp.zeros((), jnp.int32)
    return out


# ------------------------------------------------------------- reference
def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: dict):
    """YaRN inverse frequencies of the rope pairs (HF
    ``DeepseekV2YarnRotaryEmbedding``), in float64."""
    import numpy as np

    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    factor, omax = rs["factor"], rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(omax / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    mask = 1.0 - ramp
    return extra / factor * (1 - mask) + extra * mask


def _rope(x, cfg):
    """x: (B, T, H, rope) rotated at positions 0..T-1, rotate-half pairs,
    cos and sin scaled by mscale / mscale_all_dim."""
    import jax.numpy as jnp

    rs = cfg["rope_scaling"]
    t, half = x.shape[1], x.shape[-1] // 2
    freq = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    mag = yarn_mscale(rs["factor"], rs["mscale"]) \
        / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    cos = (jnp.cos(ang) * mag)[None, :, None, :]
    sin = (jnp.sin(ang) * mag)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(cfg: dict) -> float:
    _, nope, rope, _, _ = _dims(cfg)
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return m * m / math.sqrt(nope + rope)


def _attention(q, k, v, scale, dtype, chunk):
    """Causal attention, ``chunk`` query rows at a time.
    q, k: (B, T, H, Dqk); v: (B, T, H, Dv) -> (B, T, H, Dv)."""
    import jax
    import jax.numpy as jnp

    b, t, h, dqk = q.shape
    chunk = min(chunk, t)
    n = t // chunk
    qc = q.reshape(b, n, chunk, h, dqk).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = dd._mm("bqhd,bkhd->bhqk", qi, k, dtype) * scale
        qpos = i * chunk + jnp.arange(chunk)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return dd._mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      dtype)

    out = jax.lax.map(one, (jnp.arange(n), qc))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, v.shape[-1])


def mla(cfg: dict, x, lp, dtype=None, chunk: int = 512):
    """Latent attention of one layer on the normed input x: (B, T, D)."""
    import jax.numpy as jnp

    h, nope, rope, dv, r = _dims(cfg)
    b, t, _ = x.shape
    q = dd._mm("btd,de->bte", x, lp["attn/wq"], dtype)
    q = q.reshape(b, t, h, nope + rope)
    ckv = dd._mm("btd,de->bte", x, lp["attn/wkv_a"], dtype)
    c = dd._rmsnorm(ckv[..., :r], lp["attn/kv_norm/scale"])
    kv = dd._mm("btr,re->bte", c, lp["attn/wkv_b"], dtype)
    kv = kv.reshape(b, t, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    k_pe = _rope(ckv[..., None, r:], cfg)                  # (B, T, 1, rope)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, t, h, rope))], -1)
    o = _attention(q, k, kv[..., nope:], softmax_scale(cfg), dtype, chunk)
    return dd._mm("bte,ed->btd", o.reshape(b, t, h * dv), lp["attn/wo"],
                  dtype)


def _swiglu(x, w_gu, w_down, dtype):
    import jax

    gu = dd._mm("btd,kdf->kbtf", x, w_gu, dtype)
    return dd._mm("btf,fd->btd", jax.nn.silu(gu[0]) * gu[1], w_down, dtype)


def moe(cfg: dict, x, lp, dtype=None):
    """One MoE layer's share on the normed input x: (B, T, D).
    Returns (y, balance loss)."""
    import jax
    import jax.numpy as jnp

    e_all, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    held, off = cfg["n_routed_experts"], cfg["expert_offset"]
    t = x.shape[1]
    logits = dd._mm("btd,de->bte", x, lp["moe/router"], None)   # f32 router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_ids, e_all, dtype=jnp.float32)  # (B,T,k,E)
    gate = jnp.sum(chosen * top_p[..., None], axis=2)           # (B,T,E)
    count = jnp.sum(chosen, axis=(1, 2))                        # (B,E)
    f_i = e_all / (k * t) * count
    p_i = jnp.mean(probs, axis=1)
    aux = cfg["aux_loss_alpha"] * jnp.mean(jnp.sum(f_i * p_i, axis=-1))

    @jax.checkpoint
    def expert(y, args):
        w_gu, w_down, g = args                 # g: (B, T) weight or 0
        return y + g[..., None] * _swiglu(x, w_gu, w_down, dtype), None

    gates = jnp.moveaxis(gate[..., off:off + held], -1, 0)     # (held,B,T)
    w_gu = jnp.moveaxis(lp["moe/w_gu"], 1, 0)                  # (held,2,..)
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (w_gu, lp["moe/w_down"], gates))
    y = y + _swiglu(x, lp["moe/shared/w_gu"], lp["moe/shared/w_down"],
                    dtype)
    return y, aux


def _layer(cfg, x, lp, dtype, chunk, dense: bool):
    x = x + mla(cfg, dd._rmsnorm(x, lp["norm1/scale"]), lp, dtype, chunk)
    y = dd._rmsnorm(x, lp["norm2/scale"])
    if dense:
        return x + _swiglu(y, lp["ffn/w_gu"], lp["ffn/w_down"], dtype), 0.0
    out, aux = moe(cfg, y, lp, dtype)
    return x + out, aux


def loss(cfg: dict, params: dict, tokens, *, dtype=None, chunk: int = 512):
    """Mean next-token cross entropy over every position but the last,
    plus the MoE layers' balance losses."""
    import jax
    import jax.numpy as jnp

    v = cfg["vocab_size"]
    b, t = tokens.shape
    x = params["params/embed/table"][tokens]

    def group(pre):
        return {k[len(pre):]: w for k, w in params.items()
                if k.startswith(pre)}

    for i in range(cfg["first_k_dense_replace"]):
        x, _ = jax.checkpoint(
            lambda x, lp: _layer(cfg, x, lp, dtype, chunk, True))(
                x, group(f"params/lead{i}/"))

    @jax.checkpoint
    def body(carry, lp):
        x, aux = carry
        x, a = _layer(cfg, x, lp, dtype, chunk, False)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               group("params/stack/b0/"))
    x = dd._rmsnorm(x, params["params/final_norm/scale"])
    w = params["params/lm_head/w"][:, :v]
    chunk = min(chunk, t)
    n = t // chunk
    xc = x.reshape(b, n, chunk, -1).transpose(1, 0, 2, 3)
    tgt = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)],
                          axis=1)
    tc = tgt.reshape(b, n, chunk).transpose(1, 0, 2)
    valid = (jnp.arange(t) < t - 1).reshape(n, chunk)

    @jax.checkpoint
    def piece(args):
        xi, ti, ok = args
        logits = dd._mm("bcd,dv->bcv", xi, w, dtype)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return jnp.sum((lz - gold) * ok[None, :])

    total = jnp.sum(jax.lax.map(piece, (xc, tc, valid)))
    return total / (b * (t - 1)) + aux


def make_step(cfg: dict, opt: dict, *, dtype=None, rows: Optional[int] = None):
    """Jitted reference step: (state, tokens) -> (state, loss, per-leaf
    gradient norms).  ``dtype`` rounds every matmul operand but the
    router's (the control); ``rows`` keeps only the first rows of the batch
    (a planted fault)."""
    import jax
    import jax.numpy as jnp

    def step(state, tokens):
        if rows is not None:
            tokens = tokens[:rows]
        params = {k: v for k, v in state.items() if k.startswith("params/")}
        val, grads = jax.value_and_grad(
            lambda p: loss(cfg, p, tokens, dtype=dtype))(params)
        new, taken = adamw(opt, grads, state)
        norms = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in taken.items()}
        return new, val, norms

    return jax.jit(step, donate_argnums=0)


# ------------------------------------------------------------------ work
def _mla_proj(cfg: dict) -> int:
    """Multiply-adds of one token's MLA projections in one layer."""
    d = cfg["hidden_size"]
    h, nope, rope, dv, r = _dims(cfg)
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv) \
        + h * dv * d


def step_flops(cfg: dict) -> float:
    """Model FLOPs of one training step, forward and backward (3x the
    forward), without rematerialisation: causal attention once (half the
    T x T square), the head, and for the routed experts the pairs this
    chip's experts receive under balanced routing, k * held / E a token."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    f = cfg["moe_intermediate_size"]
    lead = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - lead
    per_tok = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]
    moe_layer = d * cfg["router_experts"] \
        + 3 * d * f * (cfg["n_shared_experts"] + per_tok)
    macs = cfg["num_hidden_layers"] * _mla_proj(cfg) + lead * 3 * d * ff \
        + n_moe * moe_layer + d * cfg["vocab_size"]
    tokens = tokens_per_step(cfg)
    attn = cfg["num_hidden_layers"] * attention_fwd_work(cfg)[0]
    return 3.0 * (2 * tokens * macs + attn)


def attention_fwd_work(cfg: dict):
    """(FLOPs, bytes) of one causal attention forward over the batch, one
    layer: QK^T at the query/key width and PV at the value width over the
    lower triangle; q, k (nope + rope wide), v (v wide) read once and the
    output (v wide) and its log-sum-exp written once, in the compute
    dtype."""
    h, nope, rope, dv, _ = _dims(cfg)
    dqk = nope + rope
    b, t = cfg["global_batch"], cfg["seq_len"]
    width = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    flops = 2 * b * h * (t * t / 2) * (dqk + dv)
    byts = width * b * t * h * (2 * dqk + 2 * dv) + 4 * b * h * t
    return float(flops), float(byts)


def expert_gmm_fwd_work(cfg: dict, rows: int):
    """[(FLOPs, bytes)] of the two forward grouped matmuls of one MoE layer
    over ``rows`` routed rows: gate and up (D -> 2F), then down (F -> D);
    each reads its rows and every held expert's weights once and writes
    its rows once, in the compute dtype."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    e = cfg["n_routed_experts"]
    width = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    gu = (2.0 * rows * d * 2 * f,
          float(width * (rows * d + e * d * 2 * f + rows * 2 * f)))
    down = (2.0 * rows * f * d,
            float(width * (rows * f + e * f * d + rows * d)))
    return [gu, down]


def tokens_per_step(cfg: dict) -> int:
    return cfg["global_batch"] * cfg["seq_len"]


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in shapes(cfg).values())
