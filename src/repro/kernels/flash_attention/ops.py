"""Public attention op with impl dispatch (pallas / interpret / xla / ref).

The ``xla`` path is a blockwise online-softmax written with nested
``lax.scan`` so that it has the *same working set* as the flash kernel
(never materializes a T x S score matrix).  It is what the multi-pod dry-run
lowers on CPU, so the reported HBM bytes of the compiled step reflect a
flash-style attention, and it is also a perfectly usable TPU fallback.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import next_multiple, resolve_impl
from .kernel import flash_attention_pallas
from .ref import NEG_INF, attention_ref


def _mask(iq, jk, bq, bk, offset, s, causal, window):
    qpos = iq * bq + offset + jnp.arange(bq)[:, None]
    kpos = jk * bk + jnp.arange(bk)[None, :]
    ok = kpos < s
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return ok


def _blocked(q, k, v, bq, bk):
    """Pad + reshape to blocks. Returns (qb, kb, vb, dims)."""
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    tp, sp = next_multiple(t, bq), next_multiple(s, bk)
    nq, nk = tp // bq, sp // bk
    qf = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    kf = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    vf = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    qb = qf.reshape(b, hkv, g, nq, bq, d).transpose(3, 0, 1, 2, 4, 5)
    kb = kf.reshape(b, hkv, nk, bk, d).transpose(2, 0, 1, 3, 4)
    vb = vf.reshape(b, hkv, nk, bk, dv).transpose(2, 0, 1, 3, 4)
    return qb, kb, vb, (b, hq, hkv, g, t, s, tp, sp, nq, nk, dv)


def _xla_blockwise(q, k, v, *, causal, window, scale,
                   block_q: int = 256, block_k: int = 1024,
                   return_lse: bool = False):
    b, hq, t, d = q.shape
    s = k.shape[2]
    bq = min(block_q, next_multiple(t, 8))
    bk = min(block_k, next_multiple(s, 128))
    qb, kb, vb, dims = _blocked(q, k, v, bq, bk)
    (_, _, hkv, g, _, _, tp, sp, nq, nk, dv) = dims
    offset = s - t

    def q_block(carry, iq_and_q):
        iq, qt = iq_and_q          # qt: (B, Hkv, G, bq, D)
        qt = qt * scale

        def kv_block(state, jk_and_kv):
            m, l, acc = state
            jk, kt, vt = jk_and_kv
            sc = jnp.einsum("bhgqd,bhkd->bhgqk", qt, kt)
            ok = _mask(iq, jk, bq, bk, offset, s, causal, window)
            sc = jnp.where(ok, sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vt)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        lsafe = jnp.where(l == 0, 1.0, l)
        lse = (m[..., 0] + jnp.log(lsafe[..., 0]))      # (B,Hkv,G,bq)
        return carry, (acc / lsafe, lse)

    _, (ob, lseb) = jax.lax.scan(q_block, None, (jnp.arange(nq), qb))
    out = ob.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, tp, dv)[:, :, :t, :]
    out = out.astype(q.dtype)
    if not return_lse:
        return out
    lse = lseb.transpose(1, 2, 3, 0, 4).reshape(b, hq, tp)[:, :, :t]
    return out, lse


def _xla_flash_bwd(q, k, v, o, lse, do, *, causal, window, scale,
                   block_q: int = 256, block_k: int = 1024):
    """Flash backward: recomputes p per block from the saved logsumexp;
    never materializes a T x S matrix and stores no per-block residuals."""
    b, hq, t, d = q.shape
    s = k.shape[2]
    bq = min(block_q, next_multiple(t, 8))
    bk = min(block_k, next_multiple(s, 128))
    qb, kb, vb, dims = _blocked(q, k, v, bq, bk)
    (_, _, hkv, g, _, _, tp, sp, nq, nk, dv) = dims
    offset = s - t
    dof = jnp.pad(do.astype(jnp.float32),
                  ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    dob = dof.reshape(b, hkv, g, nq, bq, dv).transpose(3, 0, 1, 2, 4, 5)
    of = jnp.pad(o.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    # D_i = rowsum(do * o)
    Df = jnp.sum(dof * of, axis=-1)                     # (B,Hq,Tp)
    Db = Df.reshape(b, hkv, g, nq, bq).transpose(3, 0, 1, 2, 4)
    lsef = jnp.pad(lse.astype(jnp.float32), ((0, 0), (0, 0), (0, tp - t)),
                   constant_values=jnp.inf)
    lseb = lsef.reshape(b, hkv, g, nq, bq).transpose(3, 0, 1, 2, 4)

    def q_block(carry, xs):
        dk_acc, dv_acc = carry
        iq, qt, dot_, Dt, Lt = xs

        def kv_block(inner, jk_and_kv):
            dq_t, dk_a, dv_a = inner
            jk, kt, vt = jk_and_kv
            sc = jnp.einsum("bhgqd,bhkd->bhgqk", qt * scale, kt)
            ok = _mask(iq, jk, bq, bk, offset, s, causal, window)
            p = jnp.where(ok, jnp.exp(sc - Lt[..., None]), 0.0)
            dv_blk = jnp.einsum("bhgqk,bhgqd->bhkd", p, dot_)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", dot_, vt)
            ds = p * (dp - Dt[..., None])
            dq_t = dq_t + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kt) * scale
            dk_blk = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qt) * scale
            dk_a = jax.lax.dynamic_update_index_in_dim(
                dk_a, dk_a[jk] + dk_blk, jk, 0)
            dv_a = jax.lax.dynamic_update_index_in_dim(
                dv_a, dv_a[jk] + dv_blk, jk, 0)
            return (dq_t, dk_a, dv_a), None

        dq0 = jnp.zeros((b, hkv, g, bq, d), jnp.float32)
        (dq_t, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_block, (dq0, dk_acc, dv_acc), (jnp.arange(nk), kb, vb))
        return (dk_acc, dv_acc), dq_t

    dk0 = jnp.zeros((nk, b, hkv, bk, d), jnp.float32)
    dv0 = jnp.zeros((nk, b, hkv, bk, dv), jnp.float32)
    (dkb, dvb), dqb = jax.lax.scan(
        q_block, (dk0, dv0), (jnp.arange(nq), qb, dob, Db, lseb))
    dq = dqb.transpose(1, 2, 3, 0, 4, 5).reshape(b, hq, tp, d)[:, :, :t, :]
    dk = dkb.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sp, d)[:, :, :s, :]
    dvv = dvb.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sp, dv)[:, :, :s, :]
    return dq.astype(q.dtype), dk.astype(k.dtype), dvv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention_core(q, k, v, causal, window, scale, impl, block_q, block_k):
    if impl == "ref":
        return attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale)
    if impl == "xla":
        return _xla_blockwise(q, k, v, causal=causal, window=window,
                              scale=scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=(impl == "interpret"))


def _attention_fwd(q, k, v, causal, window, scale, impl, block_q, block_k):
    # fwd via the dispatched impl; residuals = (q, k, v, o, lse) -- the
    # flash contract: backward recomputes p blockwise from the logsumexp.
    if impl in ("xla", "ref"):
        o, lse = _xla_blockwise(q, k, v, causal=causal, window=window,
                                scale=scale, return_lse=True)
    else:
        o, lse = flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale,
            block_q=block_q, block_k=block_k,
            interpret=(impl == "interpret"), return_lse=True)
    return o, (q, k, v, o, lse)


def _attention_bwd(causal, window, scale, impl, block_q, block_k, res, do):
    q, k, v, o, lse = res
    return _xla_flash_bwd(q, k, v, o, lse, do, causal=causal, window=window,
                          scale=scale)


_attention_core.defvjp(_attention_fwd, _attention_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "scale", "impl",
                                   "block_q", "block_k"))
def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              scale: float | None = None, impl: str | None = None,
              block_q: int = 128, block_k: int = 128):
    """Flash attention. q: (B, Hq, T, D), k: (B, Hkv, S, D), v: (B, Hkv,
    S, Dv) -> (B, Hq, T, Dv); the scale defaults to D**-0.5."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    impl = resolve_impl(impl)
    return _attention_core(q, k, v, causal, window, scale, impl,
                           block_q, block_k)
