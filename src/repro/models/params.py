"""Parameter accounting + sharding-spec resolution for whole param trees."""
from __future__ import annotations


import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.sharding import Rules, spec as axes_spec

_EXPERT_KEYS = ("w_gu", "w_down")


def _is_axes(x) -> bool:
    return (isinstance(x, tuple)
            and all(e is None or isinstance(e, str) for e in x))


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count from the abstract init (no allocation).

    ``active_only``: routed expert weights scaled by k/E (per-token
    activation); shared experts are active on every token.
    """
    from .transformer import abstract_params

    shapes, _ = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = 0.0
    frac = (cfg.experts_per_token / cfg.num_experts) if cfg.num_experts else 1.0
    for path, leaf in flat:
        n = int(np.prod(leaf.shape))
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if active_only and cfg.num_experts and any(
                k in _EXPERT_KEYS for k in keys) and "moe" in keys \
                and "shared" not in keys:
            n = int(n * frac)
        total += n
    return int(total)


def param_specs(axes_tree, rules: Rules, mesh=None, shapes=None):
    """axes pytree (+ optional matching ShapeDtypeStruct pytree) ->
    PartitionSpec pytree."""
    if shapes is None:
        return jax.tree.map(lambda ax: axes_spec(ax, rules), axes_tree,
                            is_leaf=_is_axes)
    return jax.tree.map(
        lambda ax, sh: axes_spec(ax, rules, mesh, sh.shape),
        axes_tree, shapes, is_leaf=_is_axes)


def param_shardings(axes_tree, rules: Rules, mesh, shapes=None):
    from jax.sharding import NamedSharding

    specs = param_specs(axes_tree, rules, mesh, shapes)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(
                            x, jax.sharding.PartitionSpec))
