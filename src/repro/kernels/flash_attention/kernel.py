"""Pallas TPU flash attention (causal / sliding-window, GQA).

Design (TPU-native, not a CUDA port):
  grid = (B, Hq, T/bq, S/bk) with the KV axis innermost ("arbitrary"
  iteration order semantics): the online-softmax accumulators (acc, m, l)
  live in VMEM scratch and persist across the KV-block sweep for a fixed
  (b, h, iq); the output tile is written once, on the last KV block.

  Tiles: q (bq, D), k (bk, D), v (bk, Dv) staged HBM->VMEM by BlockSpec
  (Dv may differ from D, as in latent attention's 192-wide queries and
  keys over 128-wide values); the score
  tile (bq, bk) hits the MXU via jnp.dot in f32.  bq = bk = 128 aligns every
  matmul operand to the 128x128 systolic array.  GQA is handled in the
  BlockSpec index_map (query head h reads KV head h // group), so KV tiles
  are fetched once per group from HBM, never materialized repeated.

  Causal/sliding-window blocks that are fully masked are skipped with
  pl.when -- no MXU work, no accumulator update; for causal attention this
  halves the swept area.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import next_multiple

NEG_INF = -1e30
LANES = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, window: int | None,
               offset: int, s_valid: int, bq: int, bk: int):
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    iq = pl.program_id(2)
    # absolute positions of this tile's queries / keys
    q_lo = iq * bq + offset              # first query's absolute position
    k_lo = jk * bk

    # block-level skip: is any (qpos, kpos) pair in this tile live?
    live = jnp.bool_(True)
    if causal:
        live &= k_lo <= q_lo + bq - 1    # earliest key <= latest query
    if window is not None:
        live &= k_lo + bk - 1 > q_lo - window  # latest key inside window
    live &= k_lo < s_valid               # not a fully padded KV tile

    @pl.when(live)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos < s_valid
        if causal:
            ok &= kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jk == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0, 1.0, l)    # fully-masked rows -> zeros
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # lse rides a full lane axis: a (bq,) block would put a unit dim
        # second-minor, which Mosaic refuses; the wrapper keeps lane 0
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False,
                           return_lse: bool = False):
    """q, k: (B, Hq|Hkv, T|S, D), v: (B, Hkv, S, Dv) -> (B, Hq, T, Dv)
    [, lse]."""
    b, hq, t, d = q.shape
    _, hkv, s, _ = k.shape
    dv = v.shape[-1]
    assert hq % hkv == 0
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    bq = min(block_q, next_multiple(t, 8))
    bk = min(block_k, next_multiple(s, 128))
    tp, sp = next_multiple(t, bq), next_multiple(s, bk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    grid = (b, hq, tp // bq, sp // bk)
    kern = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        offset=s - t, s_valid=s, bq=bq, bk=bk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, tp, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, tp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    out, lse = out[0][:, :, :t, :], out[1][:, :, :t, 0]
    if return_lse:
        return out, lse
    return out
