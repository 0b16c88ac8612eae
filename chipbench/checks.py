"""The comparisons that decide ``correct``.

Copied from the repository's chip smoke test (leaf digests, the q8 half
block-scale bound, exact integer leaves and data cursor) and extended with
the training comparison against the plain reference.  Nothing here imports
a tolerance or a block size from the program: the q8 codec's block is
restated below, so the bound cannot widen with a change to the codec.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .work import BLOCK
# The q8 codecs promise every restored value within half a block scale of
# the saved one: |restored - saved| <= absmax/254 (+ one ulp of absmax for
# the float rounding of the scale).  The ratio to that bound is compared
# with the configuration's own limit: 1.
HALF_SCALE_LIMIT = 1.0
# A leaf whose reference gradient is below this share of the median leaf's
# moves under Adam by round-off alone (a key bias under softmax); such
# leaves are left out of the parameter-change comparison.
STILL_LEAF_SHARE = 1e-3


# ------------------------------------------------------------ q8 bound
def half_scale_excess_np(saved: np.ndarray, restored: np.ndarray,
                         chunk: int = 1 << 22) -> float:
    """Largest ``|restored - saved| / (absmax/254 + ulp(absmax))`` over the
    256-value blocks of one leaf, exact in float64: <= 1 is within half a
    block scale."""
    x = np.ravel(saved)
    y = np.ravel(restored)
    worst = 0.0
    for lo in range(0, x.size, chunk):
        xs = x[lo:lo + chunk].astype(np.float64)
        ys = y[lo:lo + chunk].astype(np.float64)
        pad = (-xs.size) % BLOCK
        xb = np.pad(xs, (0, pad)).reshape(-1, BLOCK)
        diff = np.abs(np.pad(ys, (0, pad)).reshape(-1, BLOCK) - xb)
        absmax = np.max(np.abs(xb), axis=1, keepdims=True)
        bound = absmax / 254 + np.spacing(absmax.astype(np.float32))
        worst = max(worst, float(np.max(diff / bound)))
    return worst


@jax.jit
def _half_scale_excess_dev(saved, restored):
    x = jnp.ravel(saved).astype(jnp.float32)
    y = jnp.ravel(restored).astype(jnp.float32)
    pad = (-x.size) % BLOCK
    xb = jnp.pad(x, (0, pad)).reshape(-1, BLOCK)
    diff = jnp.abs(jnp.pad(y, (0, pad)).reshape(-1, BLOCK) - xb)
    absmax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    # one ulp of absmax: its exponent bits, times 2**-23
    expo = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(absmax, jnp.int32) & 0x7F800000,
        jnp.float32)
    bound = absmax / 254 + expo * 2.0 ** -23
    ratio = jnp.where(absmax > 0, diff / jnp.where(absmax > 0, bound, 1.0),
                      jnp.where(diff > 0, jnp.inf, 0.0))
    return jnp.max(ratio)


def half_scale_excess(saved, restored) -> float:
    """The same ratio on the device, in float32 (relative error ~1e-7,
    against a bound whose sound readings sit 1e-5 below 1)."""
    return float(_half_scale_excess_dev(saved, restored))


@jax.jit
def int4_roundtrip(x):
    """The control of a q8 codec: the same blockwise absmax scheme one
    precision down, 4-bit codes in [-7, 7]."""
    flat = jnp.ravel(x).astype(jnp.float32)
    pad = (-flat.size) % BLOCK
    xb = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
    absmax = jnp.max(jnp.abs(xb), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    q = jnp.clip(jnp.round(xb / scale), -7, 7)
    return (q * scale).reshape(-1)[:flat.size].reshape(x.shape)


# ---------------------------------------------------------- exact checks
def _fingerprint(x):
    """Two 32-bit position-weighted sums of a leaf's bits."""
    flat = jnp.ravel(x)
    if flat.dtype.itemsize != 4:
        flat = flat.astype(jnp.int32)
    bits = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    i = jnp.arange(bits.size, dtype=jnp.uint32)
    a = jnp.sum(bits * (i * jnp.uint32(2654435761) + jnp.uint32(1)),
                dtype=jnp.uint32)
    b = jnp.sum((bits ^ (i * jnp.uint32(40503))) * jnp.uint32(97),
                dtype=jnp.uint32)
    return jnp.stack([a, b])


@jax.jit
def _fingerprints(named):
    return {k: _fingerprint(v) for k, v in named.items()}


def fingerprints_async(named: Dict[str, object]) -> Dict[str, object]:
    """Leaf name -> fingerprint, dispatched to the device and not read:
    a timed path can take it without waiting, and ``read`` it later."""
    return _fingerprints(dict(named))


def read(prints: Dict[str, object]) -> Dict[str, tuple]:
    return {k: tuple(int(x) for x in np.asarray(v))
            for k, v in prints.items()}


def fingerprints(named: Dict[str, object]) -> Dict[str, tuple]:
    """Leaf name -> fingerprint, computed on the device."""
    return read(fingerprints_async(named))


def count_differing(a: Dict[str, tuple], b: Dict[str, tuple]) -> int:
    return sum(1 for k in set(a) | set(b) if a.get(k) != b.get(k))


# ------------------------------------------------------ training checks
def loss_gap(program: Iterable[float], reference: Iterable[float]) -> float:
    """Largest |program - reference| / |reference| over the steps."""
    pairs = list(zip(program, reference))
    if not pairs or any(not np.isfinite(p) for p, _ in pairs):
        return float("inf")
    return max(abs(p - r) / abs(r) for p, r in pairs)


def norm_gap(program: Dict[str, float], reference: Dict[str, float],
             leaves: Optional[Iterable[str]] = None) -> float:
    """Worst leaf's |program norm - reference norm|, over the larger of
    that leaf's reference norm and the median leaf's."""
    names = sorted(leaves if leaves is not None else reference)
    if not names:
        return 0.0
    med = statistics.median(reference[n] for n in reference)
    worst = 0.0
    for n in names:
        p = program.get(n, float("nan"))
        if not np.isfinite(p):
            return float("inf")
        worst = max(worst, abs(p - reference[n]) / max(reference[n], med))
    return worst


def moving_leaves(ref_grad_norms: Dict[str, float]) -> list:
    """Leaves the reference's first gradient moves beyond round-off."""
    med = statistics.median(ref_grad_norms.values())
    return sorted(n for n, g in ref_grad_norms.items()
                  if g >= STILL_LEAF_SHARE * med)


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number beside its limit; ``ok`` when none exceeds it."""
    out = {}
    for name, value in numbers.items():
        limit = limits[name]
        out[name] = {"value": value, "limit": limit,
                     "ok": bool(np.isfinite(value) and value <= limit)}
    return out
