"""Pure-jnp oracle for the checkpoint codec.

The codec is the TPU-native answer to "reduce the bytes iCheck's agents must
move" (DESIGN.md SS2): checkpoints are (1) block-quantized to int8 with one
f32 scale per block of 256 values, and (2) XOR-diffed against the previous
checkpoint's quantized form, so that unchanged blocks become zero bytes and
compress to nothing under zstd on the agent side.

All functions operate on *flattened, padded* buffers of shape
(num_blocks, BLOCK); padding/unpadding to that layout is done by ``ops``.
``BLOCK`` and the numpy reference live in :mod:`.blocks` (shared with the
host-side wire codec in ``repro.core.tiers`` so the two cannot drift).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .blocks import BLOCK, SCALE_HEAD_MASK

__all__ = ["BLOCK", "quantize_ref", "dequantize_ref", "xor_delta_ref",
           "quantize_delta_ref", "round_to_codes"]


def round_to_codes(x, scale):
    """int8 codes nearest ``x / scale``: the jnp twin of
    :func:`.blocks.round_to_codes_np`, used by the Pallas kernel too."""
    q = jnp.round(x / scale)
    head = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(scale, jnp.int32) & SCALE_HEAD_MASK,
        jnp.float32)
    r = (x - q * head) - q * (scale - head)
    half = 0.5 * scale
    q = q + jnp.where(r > half, 1.0, 0.0) - jnp.where(r < -half, 1.0, 0.0)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def quantize_ref(x):
    """(nb, BLOCK) float -> (int8 codes (nb, BLOCK), f32 scales (nb, 1))."""
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return round_to_codes(x, scale), scale


def dequantize_ref(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def xor_delta_ref(curr_q, prev_q):
    """Bitwise delta between two int8 code buffers (identical -> zeros)."""
    return jnp.bitwise_xor(curr_q, prev_q)


def quantize_delta_ref(x, prev_q):
    """Fused quantize + XOR-delta: what the agent receives for an
    *incremental* commit. Returns (delta codes, scales, current codes)."""
    q, scale = quantize_ref(x)
    return jnp.bitwise_xor(q, prev_q), scale, q
