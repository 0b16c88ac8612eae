"""The blockwise q8 layout — one definition shared by every codec path.

Three things implement "blockwise int8 with one f32 scale per BLOCK values":
the Pallas kernel (``kernel.py``), the jnp oracle (``ref.py``), and the
host-side wire codec (``repro.core.tiers``).  The layout constants and the
numpy reference live *here*, dependency-free (no jax import), so the host
codec and the device kernels cannot drift: ``tiers.py`` imports this module
directly and the kernel tests assert the Pallas/XLA outputs match it.

All functions operate on *flattened, padded* buffers of shape
(num_blocks, BLOCK), exactly like the kernels.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

try:  # np.dtype("bfloat16") — registered by jax's ml_dtypes dependency
    import ml_dtypes  # noqa: F401
except Exception:  # pragma: no cover - optional
    pass

BLOCK = 256  # values per quantization block (one f32 scale each)
# clears the low 12 of a scale's 23 mantissa bits: the head that is left
# times a code (7 bits) is exact in f32, and so is the tail times a code
SCALE_HEAD_MASK = ~0xFFF


def to_blocks_np(x: np.ndarray) -> Tuple[np.ndarray, int]:
    """Flatten + zero-pad to (nb, BLOCK) float32. Returns (blocks, orig_n)."""
    flat = np.ravel(x).astype(np.float32)
    n = flat.size
    nb = -(-max(n, 1) // BLOCK)
    blocks = np.zeros((nb, BLOCK), np.float32)
    blocks.reshape(-1)[:n] = flat
    return blocks, n


def round_to_codes_np(x: np.ndarray, scale: np.ndarray) -> np.ndarray:
    """int8 codes nearest ``x / scale`` (f32), clipped to [-127, 127].

    The f32 quotient alone is not enough: it can be an ulp off (a TPU's
    divide is), and at a rounding midpoint that picks the farther integer,
    an error past half a scale.  The residual ``x - q * scale``, computed
    without rounding error (scale = head + tail, each times a code exact),
    moves such a code one step back, so every code is within half a scale
    of its value on any backend.
    """
    q = np.round(x / scale)
    head = (scale.view(np.int32) & np.int32(SCALE_HEAD_MASK)).view(np.float32)
    r = (x - q * head) - q * (scale - head)
    half = np.float32(0.5) * scale
    q = q + (r > half) - (r < -half)
    return np.clip(q, -127, 127).astype(np.int8)


def quantize_np(blocks: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(nb, BLOCK) f32 -> (int8 codes (nb, BLOCK), f32 scales (nb, 1)).

    The numpy mirror of ``ref.quantize_ref`` / the Pallas quantize kernel:
    absmax/127 scale per block (1.0 for all-zero blocks), round to the
    nearest code (:func:`round_to_codes_np`), clip to [-127, 127].
    """
    blocks = blocks.astype(np.float32, copy=False)
    absmax = np.max(np.abs(blocks), axis=-1, keepdims=True)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    return round_to_codes_np(blocks, scale), scale


def dequantize_np(q: np.ndarray, scale: np.ndarray, n: int,
                  dtype) -> np.ndarray:
    """Invert :func:`quantize_np`: codes * scales, trimmed to ``n`` values.

    Float math is f32 (identical bit-for-bit to the device dequantize) and
    only the final cast goes to ``dtype``.
    """
    x = (q.astype(np.float32) * scale).reshape(-1)[:n]
    return x.astype(np.dtype(dtype))
