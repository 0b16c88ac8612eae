"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Every parameter/activation axis carries a *logical name*; a rules table maps
logical names to mesh axes.  ``spec`` resolves a tuple of logical names into
a ``PartitionSpec``, validating divisibility against the active mesh so that
a rule that does not divide (e.g. kv_heads=8 over model=16) falls back to
the axis's ``fallback`` entry (or replication) instead of failing at pjit.

Rule sets:
  TP_RULES        -- plain tensor parallelism (heads/ff/experts/vocab over
                     "model", batch over ("pod", "data")): the paper-faithful
                     baseline distribution.
  FSDP_RULES      -- TP + ZeRO-3-style weight sharding: the *param* embed
                     axis additionally shards over ("pod", "data") and is
                     all-gathered per scanned layer.  Used by archs whose
                     params do not fit a chip under plain TP.
  SEQ_RULES       -- TP + sequence parallelism on long-context activations.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    """Immutable mapping logical axis name -> mesh axes (+ fallbacks)."""

    table: Tuple[Tuple[str, MeshAxes], ...]
    fallbacks: Tuple[Tuple[str, MeshAxes], ...] = ()

    def lookup(self, name: str) -> MeshAxes:
        for k, v in self.table:
            if k == name:
                return v
        return None

    def fallback(self, name: str) -> MeshAxes:
        for k, v in self.fallbacks:
            if k == name:
                return v
        return None

    def with_rule(self, name: str, axes: MeshAxes) -> "Rules":
        table = tuple((k, v) for k, v in self.table if k != name)
        return dataclasses.replace(self, table=table + ((name, axes),))


def _axes_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def spec(logical_axes: Sequence[Optional[str]], rules: Rules,
         mesh: Optional[Mesh] = None,
         dims: Optional[Sequence[int]] = None) -> PartitionSpec:
    """Resolve logical axes to a PartitionSpec.

    If ``mesh`` and ``dims`` are given, a mapping is accepted only when the
    dimension divides evenly (pjit argument shardings reject padding); a
    non-dividing dimension falls back (then replicates).  A mesh axis is
    never used twice (first come, first served).
    """
    out = []
    used: set = set()
    for i, name in enumerate(logical_axes):
        cand = None if name is None else rules.lookup(name)
        for attempt in (cand, None if name is None else rules.fallback(name),
                        None):
            if attempt is None:
                chosen = None
                break
            ax = (attempt,) if isinstance(attempt, str) else tuple(attempt)
            if mesh is not None:
                # drop axes the mesh doesn't have (e.g. "pod" on single-pod)
                ax = tuple(a for a in ax if a in mesh.shape)
                if not ax:
                    chosen = None
                    break
            if any(a in used for a in ax):
                continue
            if mesh is not None and dims is not None:
                if dims[i] % _axes_size(mesh, ax) != 0:
                    continue
            chosen = ax[0] if len(ax) == 1 else ax
            break
        if chosen is not None:
            for a in ((chosen,) if isinstance(chosen, str) else chosen):
                used.add(a)
        out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def named_sharding(mesh: Mesh, logical_axes: Sequence[Optional[str]],
                   rules: Rules, dims: Optional[Sequence[int]] = None
                   ) -> NamedSharding:
    return NamedSharding(mesh, spec(logical_axes, rules, mesh, dims))


# --------------------------------------------------------------------------
# activation sharding constraints
# --------------------------------------------------------------------------
_ACTIVE: list = []  # stack of (mesh, rules); empty -> constraints are no-ops


class use_rules:
    """Context manager activating (mesh, rules) for ``constrain`` calls."""

    def __init__(self, mesh: Optional[Mesh], rules: Rules):
        self.pair = (mesh, rules)

    def __enter__(self):
        _ACTIVE.append(self.pair)
        return self

    def __exit__(self, *exc):
        _ACTIVE.pop()
        return False


def active_rules() -> Optional[Tuple[Optional[Mesh], Rules]]:
    return _ACTIVE[-1] if _ACTIVE else None


def constrain(x, *logical_axes: Optional[str]):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    if not _ACTIVE:
        return x
    mesh, rules = _ACTIVE[-1]
    if mesh is None:
        return x
    s = spec(logical_axes, rules, mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s))


# --------------------------------------------------------------------------
# canonical rule sets
# --------------------------------------------------------------------------
_COMMON = (
    # activations
    ("batch", ("pod", "data")),
    ("seq", None),
    ("act_embed", None),
    ("act_heads", "model"),
    ("act_kv_heads", "model"),
    ("act_ff", "model"),
    ("act_experts", "model"),
    ("act_vocab", "model"),
    ("act_rnn", "model"),
    ("kv_seq", None),
    # params
    ("embed", None),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("head_dim", None),
    ("ff", "model"),
    ("experts", "model"),
    ("expert_ff", None),
    ("kv_lora", None),                 # latent attention's KV rank
    ("vocab", "model"),
    ("rnn", "model"),
    ("conv", None),
    ("layers", None),
    ("stack", None),
)

TP_RULES = Rules(table=_COMMON,
                 fallbacks=(("act_kv_heads", None), ("kv_seq", "model")))

FSDP_RULES = Rules(
    table=tuple((k, v) for k, v in _COMMON if k != "embed")
    + (("embed", ("pod", "data")),),
    fallbacks=(("act_kv_heads", None), ("kv_seq", "model")),
)

SEQ_RULES = Rules(
    table=tuple((k, v) for k, v in _COMMON if k != "seq")
    + (("seq", "model"),),
    fallbacks=(("act_kv_heads", None), ("kv_seq", "model")),
)


def get_rules(name: str) -> Rules:
    return {"tp": TP_RULES, "fsdp": FSDP_RULES, "seq": SEQ_RULES}[name]
