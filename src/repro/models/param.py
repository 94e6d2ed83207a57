"""Parameter trees annotated with logical sharding axes.

``Annotated`` is a registered pytree whose *children* are just the value
array — the axes tuple rides along as static aux data.  That makes
``jax.eval_shape`` over init functions work without allocating parameters
(the dry-run's way of getting full-model shapes + axes), since no string
ever appears as a pytree leaf.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


@jax.tree_util.register_pytree_node_class
class Annotated:
    """A parameter leaf: array + logical axis names (one per dim)."""

    __slots__ = ("value", "axes")

    def __init__(self, value, axes: Tuple[Optional[str], ...]):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)

    def __repr__(self):
        shape = getattr(self.value, "shape", "?")
        return f"Annotated({shape}, axes={self.axes})"


def is_annotated(x) -> bool:
    return isinstance(x, Annotated)


def ann(value, *axes: Optional[str]) -> Annotated:
    if len(axes) != getattr(value, "ndim", len(axes)):
        raise ValueError(f"axes {axes} rank != value rank {value.shape}")
    return Annotated(value, tuple(axes))


def split_tree(tree):
    """(annotated tree) -> (value tree, axes tree); manual dict/tuple walk."""
    if is_annotated(tree):
        return tree.value, tree.axes
    if isinstance(tree, dict):
        vals, axes = {}, {}
        for k, v in tree.items():
            vals[k], axes[k] = split_tree(v)
        return vals, axes
    if isinstance(tree, (tuple, list)):
        if not tree:
            return type(tree)(), type(tree)()
        pairs = [split_tree(v) for v in tree]
        return (type(tree)(p[0] for p in pairs), type(tree)(p[1] for p in pairs))
    # plain leaf without annotation (shouldn't happen for params)
    return tree, tuple(None for _ in range(getattr(tree, "ndim", 0)))


def stack_periods(tree):
    """Name the leading axis of a per-period tree whose values were built
    stacked (``jax.vmap`` over the period keys) as 'layers' (for
    scan-over-periods)."""
    return jax.tree.map(lambda leaf: Annotated(leaf.value, ("layers",) + leaf.axes),
                        tree, is_leaf=is_annotated)
