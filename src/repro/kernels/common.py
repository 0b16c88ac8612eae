"""Shared kernel-dispatch machinery.

Every kernel package exposes three execution paths:

  ``pallas``     -- ``pl.pallas_call`` compiled for TPU (the production path).
  ``interpret``  -- the same kernel body executed in Pallas interpret mode on
                    CPU; used by the test suite to validate numerics against
                    the pure-jnp oracle in ``ref.py``.
  ``xla``        -- a blockwise jnp/lax implementation with the *same working
                    set* as the kernel (online softmax / chunked recurrence),
                    used when lowering on CPU (multi-pod dry-run) so that
                    ``cost_analysis()`` reflects the flash-style memory
                    behaviour rather than a naive T x T buffer.

``resolve_impl`` picks a path: the explicit argument, else the backend
(TPU -> pallas, otherwise xla).  On a TPU nothing moves a kernel off Pallas:
a kernel that does not compile there fails the program.
"""
from __future__ import annotations

VALID_IMPLS = ("pallas", "interpret", "xla", "ref")


def resolve_impl(impl: str | None = None) -> str:
    if impl is None or impl == "auto":
        import jax

        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in VALID_IMPLS:
        raise ValueError(f"impl must be one of {VALID_IMPLS} or 'auto', got {impl!r}")
    return impl


def next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
