"""Jitted wrapper: gossip-mix a pytree of stacked cluster models."""
from __future__ import annotations

import functools

import jax

from .kernel import gossip_mix_pallas
from .ref import gossip_mix_ref

__all__ = ["gossip_mix", "gossip_mix_tree"]


@functools.partial(jax.jit, static_argnames=("alpha", "impl", "interpret", "tile_m"))
def gossip_mix(y, p, alpha: int = 1, impl: str = "pallas", interpret: bool = False, tile_m: int = 512):
    """Mix one stacked (D, ...) leaf.  ``tile_m`` caps a block's lanes."""
    if impl == "ref":
        return gossip_mix_ref(y, p, alpha)
    return gossip_mix_pallas(y, p, alpha, tile_m=tile_m, interpret=interpret)


def gossip_mix_tree(tree, p, alpha: int = 1, interpret: bool = False, tile_m: int = 512):
    """Apply gossip mixing to every leaf of a (D, ...) stacked pytree."""
    return jax.tree.map(
        lambda y: gossip_mix(y, p, alpha=alpha, interpret=interpret, tile_m=tile_m),
        tree,
    )
