"""Jitted wrappers: fused SGD / normalized update over arrays or pytrees."""
from __future__ import annotations

import functools

import jax

from .kernel import normalized_update_pallas, sgd_update_pallas
from .ref import normalized_update_ref, sgd_update_ref

__all__ = ["sgd_update", "normalized_update", "sgd_update_tree"]


@functools.partial(jax.jit, static_argnames=("lr", "impl", "interpret", "tile_m"))
def sgd_update(w, g, lr: float, impl: str = "pallas", interpret: bool = False, tile_m: int = 1024):
    if impl == "ref":
        return sgd_update_ref(w, g, lr)
    return sgd_update_pallas(w, g, lr, tile_m=tile_m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("inv_theta", "impl", "interpret", "tile_m"))
def normalized_update(w_final, w_start, inv_theta: float, impl: str = "pallas",
                      interpret: bool = False, tile_m: int = 1024):
    if impl == "ref":
        return normalized_update_ref(w_final, w_start, inv_theta)
    return normalized_update_pallas(w_final, w_start, inv_theta, tile_m=tile_m, interpret=interpret)


def sgd_update_tree(params, grads, lr: float, interpret: bool = False, tile_m: int = 1024):
    """``w - lr * g`` on every leaf, each in its own layout (no flattening)."""
    return jax.tree.map(
        lambda w, g: sgd_update(w, g, lr, interpret=interpret, tile_m=tile_m),
        params, grads,
    )
