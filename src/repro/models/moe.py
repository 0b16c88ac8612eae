"""Top-k routed Mixture-of-Experts FFN over the experts this chip holds.

The layer is told which experts it holds, ``[offset, offset + held)`` of
the router's ``num_experts``, and computes their part of the result: the
expert-parallel share of one chip, run without its exchange (a layer that
holds every expert is the whole layer).  Dropless:

  1. router (f32) over every expert -> softmax -> greedy top-k ids and
     weights (renormalised over the k only with ``norm_topk_prob``),
  2. the (token, slot) pairs whose expert is held, counting-sorted by
     expert; pairs of other experts sort after them and are never computed,
  3. one grouped matmul over the sorted rows for gate and up, then one for
     down (``jax.lax.ragged_dot``: on a TPU one Mosaic kernel each, named
     ``ragged-dot-*`` in the trace), no capacity and no dropped pair,
  4. each pair's row weighted and summed back into its token.

Shared experts (a SwiGLU of width ``shared * d_ff``) run on every token and
are added once.  Also returns the per-sequence balance loss (DeepSeek's
``seq_aux``, the switch loss per sequence): ``coef * sum_i f_i P_i`` with
``f_i = E / (k T) * count_i`` and ``P_i`` the mean router probability,
averaged over sequences; and the rows each held expert computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import _dense_init, ffn_apply, ffn_init


def moe_init(key, d_model, num_experts, d_ff, *, held: int = 0,
             shared: int = 0):
    """Router over ``num_experts``; weights of ``held`` experts (0: all)."""
    kr, k1, k3, ks = jax.random.split(key, 4)
    held = held or num_experts
    p, a = {}, {}
    p["router"], a["router"] = _dense_init(kr, (d_model, num_experts),
                                           ("embed", None))
    # gate/up expert weights stacked: one grouped matmul for both
    p["w_gu"] = jax.random.normal(k1, (2, held, d_model, d_ff),
                                  jnp.float32) * d_model ** -0.5
    a["w_gu"] = ("stack", "experts", "embed", "expert_ff")
    p["w_down"] = jax.random.normal(k3, (held, d_ff, d_model),
                                    jnp.float32) * d_ff ** -0.5
    a["w_down"] = ("experts", "expert_ff", "embed")
    if shared:
        p["shared"], a["shared"] = ffn_init(ks, d_model, shared * d_ff)
    return p, a


@jax.custom_vjp
def _to_rows(x, order, dest):
    """Sorted row j takes the token of pair ``order[j]``; x: (N, D)."""
    k = order.shape[0] // x.shape[0]
    return x[order // k]


def _to_rows_fwd(x, order, dest):
    return _to_rows(x, order, dest), (order, dest, x.shape[0])


def _to_rows_bwd(res, g):
    # each token's k pairs gathered back from their rows: no scatter
    order, dest, n = res
    return g[dest].reshape(n, -1, g.shape[-1]).sum(axis=1), None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _from_rows(y, dest, order):
    """Pair i takes sorted row ``dest[i]`` (a permutation, inverse
    ``order``)."""
    return y[dest]


def _from_rows_fwd(y, dest, order):
    return y[dest], (dest, order)


def _from_rows_bwd(res, g):
    dest, order = res
    return g[order], None, None


_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


def _routed(w_gu, w_down, x, top_p, top_ids, *, offset, act):
    """The held experts' part for N tokens x: (N, D) and their top-k
    weights and router ids (N, k).  Returns ((N, D), rows per expert)."""
    n_tok, d = x.shape
    k = top_ids.shape[-1]
    held, f, _ = w_down.shape
    n = n_tok * k
    with jax.named_scope("moe/dispatch"):
        local = top_ids.reshape(n) - offset
        group = jnp.where((local >= 0) & (local < held), local, held)
        oh = jax.nn.one_hot(group, held + 1, dtype=jnp.int32)   # (n, held+1)
        counts = jnp.sum(oh, axis=0)
        rank = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=1)
        dest = (jnp.cumsum(counts) - counts)[group] + rank      # pair -> row
        order = jnp.zeros((n,), jnp.int32).at[dest].set(
            jnp.arange(n, dtype=jnp.int32))                     # row -> pair
        rows = counts[:held]
        live = (jnp.arange(n) < jnp.sum(rows))[:, None]
        xs = jnp.where(live, _to_rows(x, order, dest), 0)

    with jax.named_scope("moe/experts"):
        # rows past the held experts' are not computed by the grouped
        # matmul: masked on the way in and out, so nothing they hold
        # reaches a sum or a gradient
        w_gu = w_gu.astype(x.dtype).transpose(1, 2, 0, 3)
        gu = jax.lax.ragged_dot(xs, w_gu.reshape(held, d, 2 * f), rows)
        h = act(gu[:, :f]) * gu[:, f:]
        ys = jax.lax.ragged_dot(h, w_down.astype(x.dtype), rows)
        ys = jnp.where(live, ys, 0)

    with jax.named_scope("moe/combine"):
        # a contraction over the k slots, accumulated in f32: the (pairs, D)
        # products are never materialised in f32
        pairs = _from_rows(ys, dest, order).reshape(n_tok, k, d)
        y = jnp.einsum("nkd,nk->nd", pairs, top_p,
                       preferred_element_type=jnp.float32)
    return y.astype(x.dtype), rows


def moe_apply(params, x, *, num_experts, experts_per_token,
              expert_offset: int = 0, norm_topk_prob: bool = True,
              aux_coef=0.01, act=jax.nn.silu):
    """x: (B, T, d_model) -> (y, aux_loss, rows per held expert)."""
    b, t, d = x.shape
    k, e = experts_per_token, num_experts

    with jax.named_scope("moe/router"):
        logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                            params["router"])
        probs = jax.nn.softmax(logits, axis=-1)           # (B, T, E)
        top_p, top_ids = jax.lax.top_k(probs, k)          # (B, T, k)
        if norm_topk_prob:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        frac = jnp.mean(jax.nn.one_hot(top_ids, e, dtype=jnp.float32),
                        axis=(1, 2))                      # (B, E)
        mean_p = jnp.mean(probs, axis=1)                  # (B, E)
        aux = aux_coef * e * jnp.mean(jnp.sum(frac * mean_p, axis=-1))

    y, rows = _routed(params["w_gu"], params["w_down"], x.reshape(b * t, d),
                      top_p.reshape(b * t, k).astype(x.dtype),
                      top_ids.reshape(b * t, k), offset=expert_offset,
                      act=act)
    y = y.reshape(b, t, d)
    if "shared" in params:
        y = y + ffn_apply(params["shared"], x)
    return y, aux, rows
