"""Jitted wrapper for the cluster-aggregation kernel."""
from __future__ import annotations

import functools

import jax

from .kernel import cluster_agg_pallas
from .ref import cluster_agg_ref

__all__ = ["cluster_agg", "cluster_agg_tree"]


@functools.partial(jax.jit, static_argnames=("num_clusters", "impl", "interpret", "tile_m"))
def cluster_agg(w, weights, num_clusters: int, impl: str = "pallas",
                interpret: bool = False, tile_m: int = 512):
    """Aggregate one stacked (C, ...) leaf.  ``tile_m`` caps a block's lanes."""
    if impl == "ref":
        return cluster_agg_ref(w, weights, num_clusters)
    return cluster_agg_pallas(w, weights, num_clusters, tile_m=tile_m, interpret=interpret)


def cluster_agg_tree(tree, weights, num_clusters: int,
                     interpret: bool = False, tile_m: int = 512):
    """Aggregate a (C, ...) stacked pytree into a (D, ...) pytree."""
    return jax.tree.map(
        lambda w: cluster_agg(w, weights, num_clusters, interpret=interpret,
                              tile_m=tile_m),
        tree,
    )
