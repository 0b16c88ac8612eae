"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package ships three paths (see ``common.resolve_impl``):
``kernel.py`` -- pl.pallas_call + BlockSpec VMEM tiling (TPU production);
``ref.py``    -- pure-jnp oracle used by the test suite;
``ops.py``    -- jit'd public op with a blockwise XLA fallback that the
                 CPU multi-pod dry-run lowers (flash-style working set).

Exports resolve lazily (PEP 562): importing :mod:`repro.kernels` (or a
jax-free submodule such as ``ckpt_codec.blocks``, which the host-side wire
codec in ``repro.core.tiers`` depends on) does not import jax until a
kernel op is actually touched.  The ``rwkv6`` and ``rglru`` ops are not
exported here: their names are those of their subpackages, which replace
the attribute once imported, so import them from the subpackage.
"""
from __future__ import annotations

from importlib import import_module

_EXPORTS = {
    "attention": ".flash_attention", "attention_ref": ".flash_attention",
    "rwkv6_ref": ".rwkv6", "rglru_ref": ".rglru",
    "quantize": ".ckpt_codec", "quantize_delta": ".ckpt_codec",
    "dequantize": ".ckpt_codec", "undelta_dequantize": ".ckpt_codec",
    "resolve_impl": ".common",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    if name in _EXPORTS:
        value = getattr(import_module(_EXPORTS[name], __name__), name)
        globals()[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
