"""Jitted wrappers: fused V P^alpha B transition on arrays and pytrees."""
from __future__ import annotations

import functools

import jax

from .kernel import fused_transition_pallas
from .ref import fused_transition_ref

__all__ = ["fused_transition", "fused_transition_tree"]


@functools.partial(jax.jit, static_argnames=("alpha", "impl", "interpret", "tile_m"))
def fused_transition(w, vt, p, bt, alpha: int = 1, impl: str = "pallas",
                     interpret: bool = False, tile_m: int = 512):
    """Transition one stacked (C, ...) leaf.  ``tile_m`` caps a block's lanes."""
    if impl == "ref":
        return fused_transition_ref(w, vt, p, bt, alpha)
    return fused_transition_pallas(w, vt, p, bt, alpha, tile_m=tile_m,
                                   interpret=interpret)


def fused_transition_tree(tree, vt, p, bt, alpha: int = 1,
                          interpret: bool = False, tile_m: int = 512):
    """Apply the fused transition to every leaf of a (C, ...) stacked pytree."""
    return jax.tree.map(
        lambda w: fused_transition(w, vt, p, bt, alpha=alpha,
                                   interpret=interpret, tile_m=tile_m),
        tree,
    )
