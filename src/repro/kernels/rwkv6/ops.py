"""Public RWKV-6 op with impl dispatch.

The ``xla`` path scans over chunks with the (Dk, Dv) state as carry and
computes the intra-chunk pairwise decay tensor exactly (same math as the
Pallas kernel: every exponent is a "later minus earlier" cumulative-log-decay
difference, hence <= 0 and overflow-free for *any* decay).  The naive
k/exp(L) matmul normalization overflows for strong decays, so we trade a
(C, C, D) transient (bounded by chunk=32 here) for unconditional numerical
safety.  This is what the multi-pod dry-run lowers on CPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import next_multiple, resolve_impl
from .kernel import rwkv6_pallas
from .ref import rwkv6_ref


def _xla_chunked(r, k, v, log_w, u, s0, chunk: int = 32):
    b, h, t, d = r.shape
    c = min(chunk, next_multiple(t, 8))
    tp = next_multiple(t, c)
    pad = ((0, 0), (0, 0), (0, tp - t), (0, 0))
    rf = jnp.pad(r.astype(jnp.float32), pad)
    kf = jnp.pad(k.astype(jnp.float32), pad)
    vf = jnp.pad(v.astype(jnp.float32), pad)
    wf = jnp.pad(log_w.astype(jnp.float32), pad)
    uf = u.astype(jnp.float32)
    nc = tp // c
    # (nc, B, H, C, D)
    rb, kb, vb, wb = (x.reshape(b, h, nc, c, d).transpose(2, 0, 1, 3, 4)
                      for x in (rf, kf, vf, wf))
    mask_strict = jnp.tril(jnp.ones((c, c), bool), k=-1)

    def per_chunk(S, rkvw):
        rt, kt, vt, lw = rkvw                     # (B, H, C, D)
        L = jnp.cumsum(lw, axis=2)
        Lx = L - lw
        re = rt * jnp.exp(Lx)
        o = jnp.einsum("bhcd,bhde->bhce", re, S)
        # exact pairwise intra-chunk decays: (B, H, C_t, C_i, D), exps <= 0
        diff = Lx[:, :, :, None, :] - L[:, :, None, :, :]
        E = jnp.where(mask_strict[None, None, :, :, None],
                      jnp.exp(jnp.where(mask_strict[None, None, :, :, None],
                                        diff, 0.0)), 0.0)
        A = jnp.einsum("bhtic,bhtc,bhic->bhti", E, rt, kt)
        diag = jnp.einsum("bhtd,hd,bhtd->bht", rt, uf, kt)
        o += jnp.einsum("bhti,bhid->bhtd", A, vt)
        o += diag[..., None] * vt
        Llast = L[:, :, -1:, :]
        kend = kt * jnp.exp(Llast - L)
        S = (jnp.exp(Llast[:, :, 0, :])[..., None] * S
             + jnp.einsum("bhck,bhcv->bhkv", kend, vt))
        return S, o

    if s0 is None:
        s0 = jnp.zeros((b, h, d, d), jnp.float32)
    # checkpoint each chunk: backward recomputes the (C, C, D) pairwise
    # tensor instead of storing it per chunk (flash-style memory contract)
    S, ob = jax.lax.scan(jax.checkpoint(per_chunk),
                         s0.astype(jnp.float32), (rb, kb, vb, wb))
    o = ob.transpose(1, 2, 0, 3, 4).reshape(b, h, tp, d)
    return o[:, :, :t, :].astype(v.dtype), S


def _dispatch(r, k, v, log_w, u, s0, chunk, impl):
    if impl == "ref":
        return rwkv6_ref(r, k, v, log_w, u, s0)
    if impl == "xla":
        return _xla_chunked(r, k, v, log_w, u, s0, chunk=chunk)
    return rwkv6_pallas(r, k, v, log_w, u, s0, chunk=chunk,
                        interpret=(impl == "interpret"))


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rwkv6_core(r, k, v, log_w, u, s0, chunk, impl):
    return _dispatch(r, k, v, log_w, u, s0, chunk, impl)


def _rwkv6_fwd(r, k, v, log_w, u, s0, chunk, impl):
    out = _dispatch(r, k, v, log_w, u, s0, chunk, impl)
    return out, (r, k, v, log_w, u, s0)


def _rwkv6_bwd(chunk, impl, res, ct):
    # gradients via the chunked XLA path (the Pallas kernel shares its
    # math; a dedicated bwd kernel is the TPU production extension)
    r, k, v, log_w, u, s0 = res
    _, vjp = jax.vjp(
        lambda *args: _xla_chunked(*args, chunk=chunk), r, k, v, log_w, u, s0)
    return vjp(ct)


_rwkv6_core.defvjp(_rwkv6_fwd, _rwkv6_bwd)


@partial(jax.jit, static_argnames=("chunk", "impl"))
def rwkv6(r, k, v, log_w, u, s0=None, *, chunk: int = 64,
          impl: str | None = None):
    """RWKV-6 time-mix core. r/k/v/log_w: (B, H, T, D), log_w <= 0;
    u: (H, D).

    Returns (o: (B, H, T, D), s_final: (B, H, Dk, Dv) f32).
    """
    impl = resolve_impl(impl)
    if s0 is None:
        b, h, _, d = r.shape
        s0 = jnp.zeros((b, h, d, d), jnp.float32)
    return _rwkv6_core(r, k, v, log_w, u, s0, chunk, impl)
