"""Dense decoder family: weights and data from a seed, the plain reference,
and the work a training step needs.

Nothing here imports the program under test.  The reference is the
architecture written out in float32 ``jax.numpy`` at ``highest`` matmul
precision: embedding, then per layer RMSNorm -> GQA attention with RoPE
(optional QKV bias) -> residual -> RMSNorm -> SwiGLU -> residual, a final
RMSNorm, an untied LM head over the vocabulary, next-token cross entropy,
and AdamW.  Its parameter tree uses the leaf names the program's
``TrainState`` flattens to (``params/stack/b0/attn/wq`` ...), so that the
harness can hand the benchmark's weights to the program by name.

Memory: every layer is rematerialised and attention and the loss run in
chunks of query rows, so three reference steps at the cells' sizes fit on
one 16 GB chip once the program's state is gone.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import numpy as np

NORM_EPS = 1e-6
PAD_VOCAB = 256            # the program pads its vocabulary rows to this
# the program's ModelConfig field each configuration key sets
PROGRAM_FIELDS = {
    "num_layers": "num_hidden_layers", "d_model": "hidden_size",
    "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "qkv_bias": "qkv_bias",
    "dtype": "compute_dtype",
}


def shapes(cfg: dict) -> Dict[str, tuple]:
    """Leaf name -> shape of the parameters, as the program lays them out
    (layers stacked on a leading axis, K and V stacked, gate and up
    stacked, vocabulary rows padded to a multiple of 256)."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    n = cfg["num_hidden_layers"]
    vp = -(-cfg["vocab_size"] // PAD_VOCAB) * PAD_VOCAB
    out = {
        "params/embed/table": (vp, d),
        "params/final_norm/scale": (d,),
        "params/lm_head/w": (d, vp),
        "params/stack/b0/attn/wkv": (n, 2, d, kv * hd),
        "params/stack/b0/attn/wo": (n, h * hd, d),
        "params/stack/b0/attn/wq": (n, d, h * hd),
        "params/stack/b0/ffn/w_down": (n, ff, d),
        "params/stack/b0/ffn/w_gu": (n, 2, d, ff),
        "params/stack/b0/norm1/scale": (n, d),
        "params/stack/b0/norm2/scale": (n, d),
    }
    if cfg.get("qkv_bias"):
        out["params/stack/b0/attn/bq"] = (n, h * hd)
        out["params/stack/b0/attn/bkv"] = (n, 2, kv * hd)
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
def key_data(seed: int) -> np.ndarray:
    """A threefry key from a seed of up to 64 bits."""
    seed = int(seed)
    return np.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                      np.uint32)


def init_params(cfg: dict, kd):
    """Parameters from a key (traced: call under ``jax.jit``).

    Matrices are normal with std fan_in**-0.5, the embedding std 1, norm
    scales 1 and biases normal with std 0.02, each leaf from its own
    fold of the key."""
    import jax
    import jax.numpy as jnp

    key = jax.random.wrap_key_data(kd, impl="threefry2x32")
    out = {}
    for i, (name, shp) in enumerate(shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        leaf = name.rsplit("/", 1)[-1]
        if leaf == "scale":
            out[name] = jnp.ones(shp, jnp.float32)
        elif leaf in ("bq", "bkv"):
            out[name] = 0.02 * jax.random.normal(k, shp, jnp.float32)
        elif name == "params/embed/table":
            out[name] = jax.random.normal(k, shp, jnp.float32)
        else:
            fan_in = shp[-2]
            out[name] = jax.random.normal(k, shp, jnp.float32) \
                * fan_in ** -0.5
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


# ------------------------------------------------------------------ data
class SeededTokens:
    """Training batches from (seed, step): token ids uniform over the
    vocabulary, one Philox stream per step.  The cursor is the program's
    two-int64 data region (seed, step), so a checkpoint carries it."""

    def __init__(self, cfg: dict, seed: int):
        self.vocab = cfg["vocab_size"]
        self.seq = cfg["seq_len"]
        self.seed = int(seed)
        self.step = 0

    def batch_at(self, step: int, batch: int) -> Dict[str, np.ndarray]:
        rng = np.random.Generator(np.random.Philox(key=[self.seed, step]))
        toks = rng.integers(0, self.vocab, size=(batch, self.seq),
                            dtype=np.int64).astype(np.int32)
        return {"tokens": toks, "labels": toks}

    def next_batch(self, batch_size: int, hosts: int = 1, host_id: int = 0):
        assert hosts == 1, hosts
        out = self.batch_at(self.step, batch_size)
        self.step += 1
        return out

    def state_array(self) -> np.ndarray:
        return np.asarray([self.seed, self.step], np.int64)

    def restore(self, arr) -> None:
        a = np.asarray(arr).reshape(-1)
        self.seed, self.step = int(a[0]), int(a[1])


# ------------------------------------------------------------- reference
def _cast(x, dtype):
    """Round a matmul operand to ``dtype`` and back (None: leave it)."""
    if dtype is None:
        return x
    return x.astype(dtype).astype(x.dtype)


def _mm(eq, a, b, dtype):
    import jax
    import jax.numpy as jnp

    return jnp.einsum(eq, _cast(a, dtype), _cast(b, dtype),
                      precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * scale


def _rope(x, theta):
    """x: (B, T, H, D), rotate-half RoPE at positions 0..T-1."""
    import jax.numpy as jnp

    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq      # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, dtype, chunk):
    """Causal attention, ``chunk`` query rows at a time.
    q: (B, T, H, D); k, v: (B, T, Hkv, D) -> (B, T, H, D)."""
    import jax
    import jax.numpy as jnp

    b, t, h, d = q.shape
    chunk = min(chunk, t)
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)            # query head i reads kv head i//g
    v = jnp.repeat(v, g, axis=2)
    n = t // chunk
    qc = q.reshape(b, n, chunk, h, d).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = _mm("bqhd,bkhd->bhqk", qi, k, dtype) * d ** -0.5
        qpos = i * chunk + jnp.arange(chunk)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, dtype)

    out = jax.lax.map(one, (jnp.arange(n), qc))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, d)


def _layer(cfg, x, lp, dtype, chunk):
    import jax

    d = cfg["hidden_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    b, t, _ = x.shape
    y = _rmsnorm(x, lp["norm1/scale"])
    q = _mm("btd,de->bte", y, lp["attn/wq"], dtype)
    kv = _mm("btd,kde->kbte", y, lp["attn/wkv"], dtype)
    if "attn/bq" in lp:
        q = q + lp["attn/bq"]
        kv = kv + lp["attn/bkv"][:, None, None, :]
    q = _rope(q.reshape(b, t, h, hd), cfg["rope_theta"])
    k = _rope(kv[0].reshape(b, t, kvh, hd), cfg["rope_theta"])
    v = kv[1].reshape(b, t, kvh, hd)
    o = _attention(q, k, v, dtype, chunk).reshape(b, t, h * hd)
    x = x + _mm("bte,ed->btd", o, lp["attn/wo"], dtype)
    y = _rmsnorm(x, lp["norm2/scale"])
    gu = _mm("btd,kdf->kbtf", y, lp["ffn/w_gu"], dtype)
    hid = jax.nn.silu(gu[0]) * gu[1]
    return x + _mm("btf,fd->btd", hid, lp["ffn/w_down"], dtype)


def loss(cfg: dict, params: dict, tokens, *, dtype=None, chunk: int = 512):
    """Mean next-token cross entropy over every position but the last."""
    import jax
    import jax.numpy as jnp

    v = cfg["vocab_size"]
    b, t = tokens.shape
    x = params["params/embed/table"][tokens]
    pre = "params/stack/b0/"
    stack = {k[len(pre):]: w for k, w in params.items() if k.startswith(pre)}

    @jax.checkpoint
    def body(x, lp):
        return _layer(cfg, x, lp, dtype, chunk), None

    x, _ = jax.lax.scan(body, x, stack)
    x = _rmsnorm(x, params["params/final_norm/scale"])
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
        logits = _mm("bcd,dv->bcv", xi, w, dtype)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return jnp.sum((lz - gold) * ok[None, :])

    total = jnp.sum(jax.lax.map(piece, (xc, tc, valid)))
    return total / (b * (t - 1))


def lr_at(opt: dict, count):
    """Linear warmup then cosine decay to ``final_frac`` of the peak."""
    import jax.numpy as jnp

    base, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    c = jnp.asarray(count, jnp.float32)
    w = base * jnp.minimum(c / max(warm, 1), 1.0)
    frac = jnp.clip((c - warm) / max(total - warm, 1), 0, 1)
    cos = opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 \
        * (1 + jnp.cos(jnp.pi * frac))
    return jnp.where(c < warm, w, base * cos)


def adamw(opt: dict, grads: dict, state: dict):
    """One AdamW update of ``state`` (params, moments, count) by ``grads``
    (global-norm clipping first when ``grad_clip`` > 0).  Returns the new
    state and the gradients as the moments took them."""
    import jax.numpy as jnp

    count = state["opt/count"] + 1
    if opt["grad_clip"]:
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        grads = {k: g * scale for k, g in grads.items()}
    lr = lr_at(opt, count)
    b1, b2 = opt["b1"], opt["b2"]
    b1c = 1 - b1 ** count.astype(jnp.float32)
    b2c = 1 - b2 ** count.astype(jnp.float32)
    new = {"opt/count": count, "step": state["step"] + 1}
    for name, g in grads.items():
        rest = name[len("params/"):]
        p = state[name]
        mu = b1 * state["opt/mu/" + rest] + (1 - b1) * g
        nu = b2 * state["opt/nu/" + rest] + (1 - b2) * g * g
        upd = (mu / b1c) / (jnp.sqrt(nu / b2c) + opt["eps"]) \
            + opt["weight_decay"] * p
        new[name] = p - lr * upd
        new["opt/mu/" + rest] = mu
        new["opt/nu/" + rest] = nu
    return new, grads


def make_step(cfg: dict, opt: dict, *, dtype=None, rows: Optional[int] = None):
    """Jitted reference step: (state, tokens) -> (state, loss, per-leaf
    gradient norms).  ``dtype`` rounds every matmul operand (the control);
    ``rows`` keeps only the first rows of the batch (a planted fault)."""
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
def step_flops(cfg: dict) -> float:
    """Model FLOPs of one training step, forward and backward (3x the
    forward), without rematerialisation, with causal attention counted
    once (half the T x T square) and the head over the vocabulary."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    b, t = cfg["global_batch"], cfg["seq_len"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    dense = 2 * b * t * (n * per_layer + d * v)
    attn = n * attention_fwd_work(cfg)[0]
    return 3.0 * (dense + attn)


def attention_fwd_work(cfg: dict):
    """(FLOPs, bytes) of one causal attention forward over the batch, one
    layer: QK^T and PV over the lower triangle; q, k, v read once and the
    output and its log-sum-exp written once, in the compute dtype."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    b, t = cfg["global_batch"], cfg["seq_len"]
    width = {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]]
    flops = 2 * 2 * b * h * hd * t * t / 2
    byts = width * b * t * hd * (2 * h + 2 * kv) + 4 * b * h * t
    return float(flops), float(byts)


def tokens_per_step(cfg: dict) -> int:
    return cfg["global_batch"] * cfg["seq_len"]


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in shapes(cfg).values())
