"""The shared LM backbone: one parameterized definition covering every
architecture of ``ARCH_IDS`` (dense / MoE, with GQA or latent attention /
enc-dec audio / VLM / RWKV-6 SSM / RG-LRU hybrid).

Structure
---------
* ``init_params(cfg, key)`` -> ``(params, axes)``: params is a pytree of f32
  arrays; ``axes`` mirrors it with logical-axis tuples for the sharding
  rules.  Layer stacks are *stacked* along a leading "layers" axis and run
  with ``lax.scan`` (+ ``jax.checkpoint`` remat) so HLO size and compile
  time stay bounded at 94 layers x 512 devices.  ``abstract_params`` gives
  (ShapeDtypeStructs, axes) without allocating -- the dry-run path.
* ``forward`` / ``loss_fn``: train & scoring path.
* ``init_cache`` / ``prefill`` / ``decode_step``: serving path with KV
  caches (attention), ring buffers (sliding-window), recurrent states
  (RWKV-6 / RG-LRU) -- O(1)-in-T state for the sub-quadratic archs.

Hybrid archs scan over *super-layers* (one pattern period, e.g.
(rec, rec, attn) for recurrentgemma) plus explicit tail layers.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.sharding import constrain

from . import attention as attn
from . import mla as mla_lib
from . import moe as moe_lib
from . import rglru_layer as rglru
from . import rwkv6_layer as rwkv
from .layers import (embed_apply, embed_init, ffn_apply, ffn_init,
                     frontend_apply, frontend_init, lm_head_apply,
                     lm_head_init, rmsnorm, rmsnorm_init)

Params = Dict[str, Any]


def _is_axes(x) -> bool:
    return (isinstance(x, tuple)
            and all(e is None or isinstance(e, str) for e in x))


# ==========================================================================
# layer-stack layout per architecture
# ==========================================================================
def stack_plan(cfg: ModelConfig) -> Dict[str, Any]:
    """How layers are grouped for scan: one scanned *super-layer* holds one
    pattern period; leftovers become explicit tail layers, and an MoE
    model's leading dense layers explicit lead layers before the scan."""
    if cfg.mixer == "rwkv6":
        return dict(scan_kinds=("rwkv",), scan_len=cfg.num_layers,
                    tail_kinds=(), enc_layers=0, lead_kinds=())
    if cfg.mixer == "rglru_hybrid":
        period = cfg.pattern or ("rec", "rec", "attn")
        n_scan = cfg.num_layers // len(period)
        n_tail = cfg.num_layers - n_scan * len(period)
        tail = (cfg.tail_layers or ("rec",) * n_tail)[:n_tail]
        return dict(scan_kinds=tuple(period), scan_len=n_scan,
                    tail_kinds=tuple(tail), enc_layers=0, lead_kinds=())
    if cfg.is_encdec:
        return dict(scan_kinds=("dec",), scan_len=cfg.num_layers,
                    tail_kinds=(), enc_layers=cfg.encoder_layers,
                    lead_kinds=())
    lead = ("dense",) * (cfg.first_dense_layers if cfg.ffn == "moe" else 0)
    return dict(scan_kinds=("attn",), scan_len=cfg.num_layers - len(lead),
                tail_kinds=(), enc_layers=0, lead_kinds=lead)


def _layer_window(cfg: ModelConfig, kind: str) -> Optional[int]:
    if kind == "attn" and cfg.mixer == "rglru_hybrid":
        return cfg.window or 2048
    return cfg.window


# ==========================================================================
# per-layer blocks
# ==========================================================================
def _block_init(key, cfg: ModelConfig, kind: str):
    """``dense`` is an attention layer with a dense FFN in an MoE model."""
    ks = jax.random.split(key, 8)
    p, a = {}, {}
    p["norm1"], a["norm1"] = rmsnorm_init(cfg.d_model)
    if kind in ("attn", "dec", "dense"):
        if cfg.mla:
            p["attn"], a["attn"] = mla_lib.mla_init(ks[0], cfg)
        else:
            p["attn"], a["attn"] = attn.attn_init(
                ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias)
        if kind == "dec":
            p["norm_x"], a["norm_x"] = rmsnorm_init(cfg.d_model)
            p["xattn"], a["xattn"] = attn.attn_init(
                ks[1], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias, cross=True)
        p["norm2"], a["norm2"] = rmsnorm_init(cfg.d_model)
        if cfg.ffn == "moe" and kind != "dense":
            p["moe"], a["moe"] = moe_lib.moe_init(
                ks[2], cfg.d_model, cfg.num_experts, cfg.resolved_moe_d_ff,
                held=cfg.resolved_experts_held, shared=cfg.shared_experts)
        else:
            p["ffn"], a["ffn"] = ffn_init(ks[2], cfg.d_model, cfg.d_ff)
    elif kind == "rwkv":
        p["tm"], a["tm"] = rwkv.timemix_init(ks[0], cfg.d_model,
                                             cfg.rwkv_head_dim)
        p["norm2"], a["norm2"] = rmsnorm_init(cfg.d_model)
        p["cm"], a["cm"] = rwkv.chanmix_init(ks[1], cfg.d_model, cfg.d_ff)
    elif kind == "rec":
        p["rec"], a["rec"] = rglru.recurrent_init(
            ks[0], cfg.d_model, cfg.resolved_rnn_width, cfg.conv1d_width)
        p["norm2"], a["norm2"] = rmsnorm_init(cfg.d_model)
        p["ffn"], a["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff)
    else:
        raise ValueError(kind)
    return p, a


def _ffn_or_moe(cfg, p, h):
    """(y, aux loss, rows per held expert or None)."""
    if "moe" in p:
        return moe_lib.moe_apply(
            p["moe"], h, num_experts=cfg.num_experts,
            experts_per_token=cfg.experts_per_token,
            expert_offset=cfg.expert_offset,
            norm_topk_prob=cfg.norm_topk_prob,
            aux_coef=cfg.router_aux_coef)
    kind = "swiglu" if cfg.ffn == "moe" else cfg.ffn
    return ffn_apply(p["ffn"], h, kind=kind), jnp.zeros((), jnp.float32), \
        None


def _block_apply(cfg: ModelConfig, p: Params, x, *, kind: str, positions,
                 state, enc_out=None, impl=None, causal=True):
    """Full-sequence (train / prefill / encoder) application.

    ``state`` is None for pure training; for prefill it is this layer's
    cache slot and the updated cache is returned.
    Returns (x, aux, rows, new_state): rows per held expert of an MoE
    layer, else None."""
    window = _layer_window(cfg, kind)
    aux = jnp.zeros((), jnp.float32)
    rows = None
    new_state = state
    h = rmsnorm(p["norm1"], x)
    if kind in ("attn", "dec", "dense"):
        kw = dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                  head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                  impl=impl)
        if cfg.mla:
            self_attn = functools.partial(mla_lib.mla_apply, cfg, p["attn"],
                                          h, positions=positions, impl=impl)
        else:
            self_attn = functools.partial(attn.attn_apply, p["attn"], h,
                                          positions=positions, causal=causal,
                                          window=window, **kw)
        if state is not None:
            y, kvc = self_attn(return_cache=True)
            new_state = dict(state,
                             self=_write_prefill_cache(state["self"], kvc,
                                                       window))
        else:
            y = self_attn()
        x = x + checkpoint_name(y, "psum_out")
        if kind == "dec":
            hx = rmsnorm(p["norm_x"], x)
            y = attn.attn_apply(p["xattn"], hx, xkv=enc_out, causal=False,
                                use_rope=False, **kw)
            x = x + checkpoint_name(y, "psum_out")
            if state is not None:
                cross = attn.cross_kv(p["xattn"], enc_out, cfg.num_kv_heads,
                                      cfg.resolved_head_dim, enc_out.dtype)
                if new_state["cross"].ks is not None:   # int8 KV mode
                    kq, ksc = attn._q8(cross.k)
                    vq, vsc = attn._q8(cross.v)
                    cross = attn.KVCache(k=kq, v=vq, ks=ksc, vs=vsc)
                new_state = dict(new_state, cross=cross)
        h2 = rmsnorm(p["norm2"], x)
        y, aux, rows = _ffn_or_moe(cfg, p, h2)
        x = x + checkpoint_name(y, "psum_out")
    elif kind == "rwkv":
        st = state if state is not None else rwkv.init_state(
            x.shape[0], cfg.d_model, cfg.rwkv_head_dim, x.dtype)
        y, shift_tm, wkv_new = rwkv.timemix_apply(
            p["tm"], h, st.shift_tm, st.wkv, cfg.rwkv_head_dim, impl=impl)
        x = x + checkpoint_name(y, "psum_out")
        h2 = rmsnorm(p["norm2"], x)
        y, shift_cm = rwkv.chanmix_apply(p["cm"], h2, st.shift_cm)
        x = x + checkpoint_name(y, "psum_out")
        new_state = rwkv.RWKVState(shift_tm=shift_tm.astype(st.shift_tm.dtype),
                                   shift_cm=shift_cm.astype(st.shift_cm.dtype),
                                   wkv=wkv_new)
        if state is None:
            new_state = None
    elif kind == "rec":
        st = state if state is not None else rglru.init_state(
            x.shape[0], cfg.resolved_rnn_width, cfg.conv1d_width, x.dtype)
        y, new_state = rglru.recurrent_apply(p["rec"], h, st, impl=impl)
        x = x + checkpoint_name(y, "psum_out")
        h2 = rmsnorm(p["norm2"], x)
        x = x + checkpoint_name(ffn_apply(p["ffn"], h2, kind=cfg.ffn),
                                "psum_out")
        if state is None:
            new_state = None
    return constrain(x, "batch", "seq", "act_embed"), aux, rows, new_state


def _write_prefill_cache(cache: attn.KVCache, kvc: attn.KVCache, window):
    """Store prefill K/V into the (possibly ring, possibly int8) buffer."""
    s_max = cache.k.shape[2]
    t = kvc.k.shape[2]
    k_in, v_in = kvc.k, kvc.v
    ks = vs = None
    if cache.ks is not None:                     # int8 KV mode
        k_in, ks = attn._q8(k_in)
        v_in, vs = attn._q8(v_in)
    if t > s_max:
        # ring buffer: keep the last `s_max` positions, rotated so absolute
        # position p lives in slot p % s_max (matching decode's ring writes)
        shift = (t - s_max) % s_max

        def roll(x):
            return jnp.roll(x[:, :, t - s_max:, :], shift, axis=2)

        return attn.KVCache(
            k=roll(k_in).astype(cache.k.dtype),
            v=roll(v_in).astype(cache.v.dtype),
            ks=None if ks is None else roll(ks),
            vs=None if vs is None else roll(vs))
    def dus(buf, val):
        return jax.lax.dynamic_update_slice(
            buf, val.astype(buf.dtype), (0, 0, 0, 0))

    return attn.KVCache(
        k=dus(cache.k, k_in), v=dus(cache.v, v_in),
        ks=None if ks is None else dus(cache.ks, ks),
        vs=None if vs is None else dus(cache.vs, vs))


def _block_decode(cfg: ModelConfig, p: Params, x, idx, *, kind: str,
                  state, impl=None):
    """One-token decode. x: (B, 1, D). Returns (x, new_state)."""
    window = _layer_window(cfg, kind)
    h = rmsnorm(p["norm1"], x)
    if kind in ("attn", "dec", "dense"):
        kw = dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                  head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta)
        if cfg.mla:
            y, kvc = mla_lib.mla_decode(cfg, p["attn"], h, state["self"],
                                        idx)
        else:
            y, kvc = attn.attn_decode(p["attn"], h, state["self"], idx,
                                      window=window, **kw)
        state = dict(state, self=kvc)
        x = x + y
        if kind == "dec":
            hx = rmsnorm(p["norm_x"], x)
            y, _ = attn.attn_decode(p["xattn"], hx, state["cross"], idx,
                                    cross=True, use_rope=False, **kw)
            x = x + y
        h2 = rmsnorm(p["norm2"], x)
        y, _, _ = _ffn_or_moe(cfg, p, h2)
        x = x + y
    elif kind == "rwkv":
        y, shift_tm, wkv_new = rwkv.timemix_apply(
            p["tm"], h, state.shift_tm, state.wkv, cfg.rwkv_head_dim,
            impl=impl)
        x = x + y
        h2 = rmsnorm(p["norm2"], x)
        y, shift_cm = rwkv.chanmix_apply(p["cm"], h2, state.shift_cm)
        x = x + y
        state = rwkv.RWKVState(shift_tm=shift_tm.astype(state.shift_tm.dtype),
                               shift_cm=shift_cm.astype(state.shift_cm.dtype),
                               wkv=wkv_new)
    elif kind == "rec":
        y, state = rglru.recurrent_apply(p["rec"], h, state, impl=impl)
        x = x + y
        h2 = rmsnorm(p["norm2"], x)
        x = x + ffn_apply(p["ffn"], h2, kind=cfg.ffn)
    return x, state


# ==========================================================================
# parameter init
# ==========================================================================
def _super_init(key, cfg, kinds):
    p, a = {}, {}
    ks = jax.random.split(key, len(kinds))
    for i, kind in enumerate(kinds):
        p[f"b{i}"], a[f"b{i}"] = _block_init(ks[i], cfg, kind)
    return p, a


def _stacked_init(key, cfg, kinds, n):
    keys = jax.random.split(key, n)
    holder = {}

    def init_only(k):
        p, a = _super_init(k, cfg, kinds)
        holder["axes"] = a                 # static, captured during trace
        return p

    stacked = jax.vmap(init_only)(keys)
    axes = jax.tree.map(lambda ax: ("layers",) + tuple(ax), holder["axes"],
                        is_leaf=_is_axes)
    return stacked, axes


def init_params(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    plan = stack_plan(cfg)
    n_tail = len(plan["tail_kinds"])
    ks = jax.random.split(key, 6 + n_tail + len(plan["lead_kinds"]))
    p, a = {}, {}
    p["embed"], a["embed"] = embed_init(ks[0], cfg.padded_vocab, cfg.d_model)
    if cfg.frontend in ("frames", "patches"):
        p["frontend"], a["frontend"] = frontend_init(ks[1], cfg.d_model,
                                                     cfg.d_model)
    if plan["enc_layers"]:
        p["enc"], a["enc"] = _stacked_init(ks[2], cfg, ("attn",),
                                           plan["enc_layers"])
        p["enc_norm"], a["enc_norm"] = rmsnorm_init(cfg.d_model)
    p["stack"], a["stack"] = _stacked_init(ks[3], cfg, plan["scan_kinds"],
                                           plan["scan_len"])
    for i, kind in enumerate(plan["tail_kinds"]):
        p[f"tail{i}"], a[f"tail{i}"] = _block_init(ks[6 + i], cfg, kind)
    for i, kind in enumerate(plan["lead_kinds"]):
        p[f"lead{i}"], a[f"lead{i}"] = _block_init(ks[6 + n_tail + i], cfg,
                                                   kind)
    p["final_norm"], a["final_norm"] = rmsnorm_init(cfg.d_model)
    p["lm_head"], a["lm_head"] = lm_head_init(ks[5], cfg.d_model,
                                              cfg.padded_vocab)
    return p, a


def abstract_params(cfg: ModelConfig):
    """(ShapeDtypeStruct pytree, axes pytree) without touching devices."""
    holder = {}

    def f(k):
        p, a = init_params(cfg, k)
        holder["axes"] = a
        return p

    shapes = jax.eval_shape(f, jax.random.key(0))
    return shapes, holder["axes"]


# ==========================================================================
# forward (train / score)
# ==========================================================================
def _remat(f, policy: str):
    if policy == "none":
        return f
    if policy == "dots":
        return jax.checkpoint(
            f,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if policy == "psum":
        # hillclimb H3: save exactly the post-all-reduce block outputs --
        # the backward then never re-runs the forward's TP collectives
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.save_only_these_names(
                "psum_out"))
    return jax.checkpoint(f)


def _embed_inputs(cfg: ModelConfig, params, batch):
    tokens = batch["tokens"]
    b, t = tokens.shape
    x = embed_apply(params["embed"], tokens).astype(cfg.dtype)
    prefix = 0
    if cfg.frontend == "patches":
        pe = frontend_apply(params["frontend"],
                            batch["patches"].astype(cfg.dtype))
        x = jnp.concatenate([pe, x], axis=1)
        prefix = pe.shape[1]
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), (b, x.shape[1]))
    return x, positions, prefix


def _run_encoder(cfg, params, batch, impl):
    enc_in = frontend_apply(params["frontend"],
                            batch["frames"].astype(cfg.dtype))
    b, s, _ = enc_in.shape
    epos = jnp.broadcast_to(jnp.arange(s), (b, s))

    def ebody(e, lp):
        e, _, _, _ = _block_apply(cfg, lp["b0"], e, kind="attn",
                                  positions=epos, state=None, impl=impl,
                                  causal=False)
        return e, None

    e, _ = jax.lax.scan(_remat(ebody, cfg.remat_policy), enc_in,
                        params["enc"])
    return rmsnorm(params["enc_norm"], e)


def _run_stack(cfg, params, x, positions, *, plan, impl, enc_out=None,
               caches=None):
    """Lead layers, the scanned super-layer stack, tail layers.

    ``caches`` is None (training) or {"lead": [...], "stack": stacked-cache,
    "tails": [...]} (prefill).  Returns (x, aux, rows, new_caches): rows is
    (scanned layers, held experts) for an MoE stack, else None."""
    kinds = plan["scan_kinds"]
    aux = jnp.zeros((), jnp.float32)
    new_lead = []
    for i, kind in enumerate(plan["lead_kinds"]):
        st = None if caches is None else caches["lead"][i]
        block = _remat(functools.partial(_block_apply, cfg, kind=kind,
                                         positions=positions, impl=impl),
                       cfg.remat_policy)
        x, aux_i, _, st = block(params[f"lead{i}"], x, state=st)
        aux = aux + aux_i
        new_lead.append(st)

    def body(carry, xs):
        x, aux = carry
        lp, cache_in = xs
        new_cache = cache_in
        rows = None
        for i, kind in enumerate(kinds):
            st = None if cache_in is None else cache_in[f"b{i}"]
            x, aux_i, rows_i, st = _block_apply(
                cfg, lp[f"b{i}"], x, kind=kind, positions=positions,
                state=st, enc_out=enc_out, impl=impl)
            aux = aux + aux_i
            rows = rows_i if rows is None else rows
            if cache_in is not None:
                new_cache = dict(new_cache, **{f"b{i}": st})
        return (x, aux), (new_cache, rows)

    (x, aux), (new_stack, rows) = jax.lax.scan(
        _remat(body, cfg.remat_policy), (x, aux),
        (params["stack"], caches["stack"] if caches else None))
    new_tails = []
    for i, kind in enumerate(plan["tail_kinds"]):
        st = None if caches is None else caches["tails"][i]
        x, aux_i, _, st = _block_apply(cfg, params[f"tail{i}"], x, kind=kind,
                                       positions=positions, state=st,
                                       enc_out=enc_out, impl=impl)
        aux = aux + aux_i
        new_tails.append(st)
    new_caches = None if caches is None else dict(
        caches, lead=new_lead, stack=new_stack, tails=new_tails)
    return x, aux, rows, new_caches


def _forward(cfg: ModelConfig, params, batch, impl):
    plan = stack_plan(cfg)
    x, positions, prefix = _embed_inputs(cfg, params, batch)
    enc_out = _run_encoder(cfg, params, batch, impl) if plan["enc_layers"] \
        else None
    x, aux, rows, _ = _run_stack(cfg, params, x, positions, plan=plan,
                                 impl=impl, enc_out=enc_out)
    x = rmsnorm(params["final_norm"], x)
    if prefix:
        x = x[:, prefix:, :]
    logits = lm_head_apply(params["lm_head"], x,
                           valid_vocab=cfg.vocab_size)
    return logits, aux, rows


def forward(cfg: ModelConfig, params, batch, *, impl: Optional[str] = None):
    """Training / scoring forward pass. Returns (logits, aux_loss)."""
    logits, aux, _ = _forward(cfg, params, batch, impl)
    return logits, aux


def loss_fn(cfg: ModelConfig, params, batch, *, impl: Optional[str] = None):
    """Next-token cross-entropy (+ MoE aux). Returns (loss, metrics); an
    MoE model's metrics carry ``moe_rows``, the rows each held expert
    computed in each scanned layer (a device array)."""
    logits, aux, rows = _forward(cfg, params, batch, impl)
    labels = batch["labels"]
    logits = logits[:, :-1, :].astype(jnp.float32)
    targets = labels[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                               axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    xent = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = xent + aux
    metrics = {"xent": xent, "aux": aux}
    if rows is not None:
        metrics["moe_rows"] = rows
    return loss, metrics


# ==========================================================================
# serving: cache init / prefill / decode
# ==========================================================================
def _kind_cache_init(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype):
    window = _layer_window(cfg, kind)
    if kind in ("attn", "dense") and cfg.mla:
        return {"self": mla_lib.init_cache(cfg, batch, max_len, dtype)}
    if kind in ("attn", "dense"):
        s = min(max_len, window) if window else max_len
        return {"self": attn.init_kv_cache(batch, cfg.num_kv_heads, s,
                                           cfg.resolved_head_dim, dtype,
                                           quant=cfg.kv_quant)}
    if kind == "dec":
        return {"self": attn.init_kv_cache(batch, cfg.num_kv_heads, max_len,
                                           cfg.resolved_head_dim, dtype,
                                           quant=cfg.kv_quant),
                "cross": attn.init_kv_cache(batch, cfg.num_kv_heads,
                                            cfg.num_frames,
                                            cfg.resolved_head_dim, dtype,
                                            quant=cfg.kv_quant)}
    if kind == "rwkv":
        return rwkv.init_state(batch, cfg.d_model, cfg.rwkv_head_dim, dtype)
    if kind == "rec":
        return rglru.init_state(batch, cfg.resolved_rnn_width,
                                cfg.conv1d_width, dtype)
    raise ValueError(kind)


def _kind_cache_axes(kind: str, quant: bool = False):
    if kind in ("attn", "dense"):
        return {"self": attn.cache_axes(quant)}
    if kind == "dec":
        return {"self": attn.cache_axes(quant),
                "cross": attn.cache_axes(quant)}
    if kind == "rwkv":
        return rwkv.state_axes()
    if kind == "rec":
        return rglru.state_axes()
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict[str, Any]:
    """Decode cache pytree for a batch of ``batch`` sequences."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    plan = stack_plan(cfg)
    single = {f"b{i}": _kind_cache_init(cfg, kind, batch, max_len, dtype)
              for i, kind in enumerate(plan["scan_kinds"])}
    n = plan["scan_len"]
    stacked = jax.tree.map(
        lambda leaf: jnp.zeros((n,) + leaf.shape, leaf.dtype), single)
    tails = [_kind_cache_init(cfg, kind, batch, max_len, dtype)
             for kind in plan["tail_kinds"]]
    lead = [_kind_cache_init(cfg, kind, batch, max_len, dtype)
            for kind in plan["lead_kinds"]]
    return {"lead": lead, "stack": stacked, "tails": tails,
            "idx": jnp.zeros((), jnp.int32)}


def cache_axes(cfg: ModelConfig) -> Dict[str, Any]:
    """Logical-axis pytree matching ``init_cache`` output."""
    plan = stack_plan(cfg)
    single = {f"b{i}": _kind_cache_axes(kind, cfg.kv_quant)
              for i, kind in enumerate(plan["scan_kinds"])}
    stacked = jax.tree.map(lambda ax: ("layers",) + tuple(ax), single,
                           is_leaf=_is_axes)
    tails = [_kind_cache_axes(kind, cfg.kv_quant)
             for kind in plan["tail_kinds"]]
    lead = [_kind_cache_axes(kind, cfg.kv_quant)
            for kind in plan["lead_kinds"]]
    return {"lead": lead, "stack": stacked, "tails": tails, "idx": ()}


def prefill(cfg: ModelConfig, params, batch, cache, *,
            impl: Optional[str] = None):
    """Run the prompt through the model, filling ``cache``.

    Returns (logits_last: (B, vocab), new_cache)."""
    plan = stack_plan(cfg)
    x, positions, prefix = _embed_inputs(cfg, params, batch)
    enc_out = _run_encoder(cfg, params, batch, impl) if plan["enc_layers"] \
        else None
    x, _, _, caches = _run_stack(cfg, params, x, positions, plan=plan,
                                 impl=impl, enc_out=enc_out, caches=cache)
    x = rmsnorm(params["final_norm"], x)
    logits = lm_head_apply(params["lm_head"], x[:, -1:, :],
                           valid_vocab=cfg.vocab_size)[:, 0, :]
    caches = dict(caches, idx=jnp.asarray(x.shape[1], jnp.int32))
    return logits, caches


def decode_step(cfg: ModelConfig, params, cache, tokens, *,
                impl: Optional[str] = None):
    """One decoding step. tokens: (B, 1) -> (logits (B, vocab), new_cache)."""
    plan = stack_plan(cfg)
    kinds = plan["scan_kinds"]
    x = embed_apply(params["embed"], tokens).astype(cfg.dtype)
    idx = cache["idx"]
    new_lead = []
    for i, kind in enumerate(plan["lead_kinds"]):
        x, st = _block_decode(cfg, params[f"lead{i}"], x, idx, kind=kind,
                              state=cache["lead"][i], impl=impl)
        new_lead.append(st)

    def body(x, xs):
        lp, lc = xs
        new_lc = lc
        for i, kind in enumerate(kinds):
            x, st = _block_decode(cfg, lp[f"b{i}"], x, idx, kind=kind,
                                  state=lc[f"b{i}"], impl=impl)
            new_lc = dict(new_lc, **{f"b{i}": st})
        return x, new_lc

    x, new_stack = jax.lax.scan(body, x, (params["stack"], cache["stack"]))
    new_tails = []
    for i, kind in enumerate(plan["tail_kinds"]):
        x, st = _block_decode(cfg, params[f"tail{i}"], x, idx, kind=kind,
                              state=cache["tails"][i], impl=impl)
        new_tails.append(st)
    x = rmsnorm(params["final_norm"], x)
    logits = lm_head_apply(params["lm_head"], x,
                           valid_vocab=cfg.vocab_size)[:, 0, :]
    new_cache = dict(cache, lead=new_lead, stack=new_stack, tails=new_tails,
                     idx=idx + 1)
    return logits, new_cache
