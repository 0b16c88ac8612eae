"""Public, jit-friendly checkpoint-codec ops (pad/flatten + impl dispatch).

``repro.core.snapshot.snapshot_pytree(codec="q8"|"q8-delta")`` calls one
of these on the commit hot path: every float region part goes through
:func:`quantize` *on device*, before the D2H copy, so the host — and then
the client→agent fabric and every storage tier — moves int8 codes + 1/256
overhead of f32 scales instead of the raw f32 payload (~4x fewer bytes;
for ``q8-delta`` the host counts the changed blocks against the catalog's
previous codes and XORs only those into sparse frames, where unchanged
blocks cost zero wire bytes).  Shape/dtype restoration metadata and the
delta-frame bookkeeping travel in ``RegionMeta`` (``dtype``,
``partition``, plus ``frame``/``chain`` on the per-checkpoint copies).
:func:`quantize_delta` (codes and their XOR against previous codes in one
pass) and :func:`undelta_dequantize` (the device-side replay primitive
that folds a delta frame back onto the previous codes) are not on the save
or restore path; the host restart path
(``repro.core.tiers.q8_chain_decode``) applies the same XOR + dequantize
in numpy, asserted bit-identical in the test suite.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..common import resolve_impl
from . import kernel as K
from . import ref as R
from . import rs_kernel as RS
from .ref import BLOCK


def _to_blocks(x):
    """Flatten + zero-pad to (nb, BLOCK). Returns (blocks, orig_size)."""
    flat = jnp.ravel(x)
    n = flat.shape[0]
    nb = -(-n // BLOCK)
    flat = jnp.pad(flat, (0, nb * BLOCK - n))
    return flat.reshape(nb, BLOCK), n


@partial(jax.jit, static_argnames=("impl",))
def quantize(x, impl: str | None = None):
    """Array -> (codes int8 (nb, BLOCK), scales f32 (nb, 1)).

    Shape/dtype restoration metadata travels with the caller (RegionMeta).
    """
    blocks, _ = _to_blocks(x)
    impl = resolve_impl(impl)
    if impl in ("xla", "ref"):
        return R.quantize_ref(blocks)
    return K.quantize_pallas(blocks, interpret=(impl == "interpret"))


@partial(jax.jit, static_argnames=("impl",))
def quantize_delta(x, prev_q, impl: str | None = None):
    """Array + previous codes -> (delta int8, scales f32, codes int8)."""
    blocks, _ = _to_blocks(x)
    impl = resolve_impl(impl)
    if impl in ("xla", "ref"):
        return R.quantize_delta_ref(blocks, prev_q)
    return K.quantize_delta_pallas(blocks, prev_q, interpret=(impl == "interpret"))


@partial(jax.jit, static_argnames=("shape", "dtype", "impl"))
def dequantize(q, scale, shape, dtype=jnp.float32, impl: str | None = None):
    impl = resolve_impl(impl)
    if impl in ("xla", "ref"):
        blocks = R.dequantize_ref(q, scale, dtype)
    else:
        blocks = K.dequantize_pallas(q, scale, dtype,
                                     interpret=(impl == "interpret"))
    n = int(np.prod(shape)) if shape else 1
    return jnp.ravel(blocks)[:n].reshape(shape)


@partial(jax.jit, static_argnames=("shape", "dtype", "impl"))
def undelta_dequantize(delta, prev_q, scale, shape, dtype=jnp.float32,
                       impl: str | None = None):
    """Invert a delta commit: codes = delta ^ prev_q, then dequantize."""
    return dequantize(jnp.bitwise_xor(delta, prev_q), scale, shape, dtype,
                      impl=impl)


@partial(jax.jit, static_argnames=("m", "impl"))
def rs_encode(data_rows, m: int = 1, impl: str | None = None):
    """Reed-Solomon parity: (k, stride) uint8 data -> (m, stride) parity.

    The device-side twin of :func:`repro.kernels.ckpt_codec.rs.rs_encode_np`
    (asserted bit-identical in the test suite); the erasure-coded L1
    durability path in ``repro.core.tiers`` runs the numpy reference on the
    host, this op exists for on-device encode ahead of the D2H copy.
    """
    x = data_rows.astype(jnp.int32)
    impl = resolve_impl(impl)
    if impl in ("xla", "ref"):
        parity = RS.rs_encode_ref(x, m)
    else:
        parity = RS.rs_encode_pallas(x, m, interpret=(impl == "interpret"))
    return parity.astype(jnp.uint8)
