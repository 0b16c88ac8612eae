"""Pallas TPU kernel for the checkpoint codec (blockwise int8 quantization +
XOR delta against the previous checkpoint's codes).

Tiling: the flattened checkpoint buffer is shaped (num_blocks, BLOCK=256);
each grid step processes a (ROWS_PER_TILE, 256) tile held in VMEM -- 256
lanes = 2 VREG lanes wide, rows a multiple of 8 sublanes, so the tile is
hardware-aligned.  The whole codec is a single pass over HBM: read x (and
prev codes for the delta variant), write int8 codes + f32 scales.  Arithmetic
intensity is O(1) so the kernel is HBM-bandwidth-bound by design -- the point
is to emit 4x fewer bytes for the agent transfer than a raw f32 snapshot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .blocks import BLOCK
from .ref import round_to_codes

ROWS_PER_TILE = 64  # (64, 256) f32 tile = 64 KiB in VMEM


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q_ref[...] = round_to_codes(x, scale)
    s_ref[...] = scale


def _quantize_delta_kernel(x_ref, prev_ref, d_ref, s_ref, q_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = round_to_codes(x, scale)
    q_ref[...] = q
    d_ref[...] = jnp.bitwise_xor(q, prev_ref[...])
    s_ref[...] = scale


def _dequantize_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]) \
        .astype(x_ref.dtype)


def _pad_rows(x, rows):
    nb = x.shape[0]
    up = pl.cdiv(nb, rows) * rows
    if up == nb:
        return x
    return jax.numpy.pad(x, ((0, up - nb),) + ((0, 0),) * (x.ndim - 1))


def quantize_pallas(x, *, interpret: bool = False):
    """x: (nb, BLOCK) float -> (codes int8 (nb, BLOCK), scales f32 (nb, 1))."""
    nb = x.shape[0]
    rows = min(ROWS_PER_TILE, nb)
    x = _pad_rows(x, rows)          # whole tiles only: no OOB reads
    nbp = x.shape[0]
    grid = (nbp // rows,)
    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nbp, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((nbp, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return q[:nb], s[:nb]


def quantize_delta_pallas(x, prev_q, *, interpret: bool = False):
    """Fused quantize + XOR delta. Returns (delta, scales, codes)."""
    nb = x.shape[0]
    rows = min(ROWS_PER_TILE, nb)
    x = _pad_rows(x, rows)
    prev_q = _pad_rows(prev_q, rows)
    nbp = x.shape[0]
    grid = (nbp // rows,)
    d, s, q = pl.pallas_call(
        _quantize_delta_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((rows, BLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rows, BLOCK), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nbp, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((nbp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((nbp, BLOCK), jnp.int8)],
        interpret=interpret,
    )(x, prev_q)
    return d[:nb], s[:nb], q[:nb]


def dequantize_pallas(q, scale, dtype=jnp.float32, *, interpret: bool = False):
    nb = q.shape[0]
    rows = min(ROWS_PER_TILE, nb)
    q = _pad_rows(q, rows)
    scale = _pad_rows(scale, rows)
    nbp = q.shape[0]
    grid = (nbp // rows,)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, BLOCK), dtype),
        interpret=interpret,
    )(q, scale)
    return out[:nb]
