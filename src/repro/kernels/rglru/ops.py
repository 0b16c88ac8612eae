"""Public RG-LRU op with impl dispatch.

The ``xla`` path uses ``lax.associative_scan`` over (a, g) pairs -- the
log-depth formulation XLA lowers to an efficient parallel scan; memory is
O(T * D) (no pairwise tensor), which is what the dry-run lowers on CPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import resolve_impl
from .kernel import rglru_pallas
from .ref import rglru_ref


def _xla_assoc(log_a, g, h0=None):
    la = log_a.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    if h0 is not None:
        gf = gf.at[:, 0, :].add(jnp.exp(la[:, 0, :]) * h0.astype(jnp.float32))

    def combine(x, y):
        ax, gx = x
        ay, gy = y
        return ax + ay, jnp.exp(ay) * gx + gy

    _, h = jax.lax.associative_scan(combine, (la, gf), axis=1)
    return h.astype(g.dtype), h[:, -1, :].astype(jnp.float32)


def _dispatch(log_a, g, h0, chunk, impl):
    if impl == "ref":
        return rglru_ref(log_a, g, h0)
    if impl == "xla":
        return _xla_assoc(log_a, g, h0)
    return rglru_pallas(log_a, g, h0, chunk=chunk,
                        interpret=(impl == "interpret"))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rglru_core(log_a, g, h0, chunk, impl):
    return _dispatch(log_a, g, h0, chunk, impl)


def _rglru_fwd(log_a, g, h0, chunk, impl):
    h, h_last = _dispatch(log_a, g, h0, chunk, impl)
    return (h, h_last), (log_a, g, h0, h)


def _rglru_bwd(chunk, impl, res, ct):
    """Analytic adjoint of the diagonal recurrence via a reverse
    associative scan -- O(T * D) memory, no stored combine tree.

      lam_t = dh_t + a_{t+1} lam_{t+1}
      dg_t = lam_t;  dlog_a_t = lam_t * h_{t-1} * a_t;  dh0 = a_0 lam_0
    """
    log_a, g, h0, h = res
    dh, dh_last = ct
    la = log_a.astype(jnp.float32)
    dhf = dh.astype(jnp.float32)
    dhf = dhf.at[:, -1, :].add(dh_last.astype(jnp.float32))

    # reverse scan: lam_t = dh_t + a_{t+1} * lam_{t+1}
    a_next = jnp.concatenate(
        [la[:, 1:, :], jnp.full_like(la[:, :1, :], -jnp.inf)], axis=1)

    def combine(x, y):
        ax, lx = x
        ay, ly = y
        return ax + ay, jnp.exp(ay) * lx + ly

    _, lam = jax.lax.associative_scan(combine, (a_next, dhf), axis=1,
                                      reverse=True)
    hf = h.astype(jnp.float32)
    h0f = jnp.zeros_like(hf[:, 0, :]) if h0 is None \
        else h0.astype(jnp.float32)
    h_prev = jnp.concatenate([h0f[:, None, :], hf[:, :-1, :]], axis=1)
    a = jnp.exp(la)
    dlog_a = lam * h_prev * a
    dg = lam.astype(g.dtype)
    dh0 = None if h0 is None else (lam[:, 0, :] * a[:, 0, :]).astype(h0.dtype)
    return dlog_a.astype(log_a.dtype), dg, dh0


_rglru_core.defvjp(_rglru_fwd, _rglru_bwd)


@partial(jax.jit, static_argnames=("chunk", "impl"))
def rglru(log_a, g, h0=None, *, chunk: int = 64, impl: str | None = None):
    """RG-LRU core: h_t = exp(log_a_t) * h_{t-1} + g_t.

    log_a, g: (B, T, D); h0: (B, D) or None.
    Returns (h: (B, T, D), h_final: (B, D) f32).
    """
    impl = resolve_impl(impl)
    return _rglru_core(log_a, g, h0, chunk, impl)
