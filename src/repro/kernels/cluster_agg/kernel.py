"""Pallas TPU kernel: intra-cluster weighted aggregation (eq. 2).

Stacked client models ``W`` (C, ...) are reduced to cluster models
``Y`` (D, ...) with per-client weights ``m^_i`` inside contiguous, uniform
clusters of size ``g = C / D``:

    Y[d] = sum_{i in cluster d} m^_i * W[i]

Bandwidth-bound streaming reduction: each grid step loads one VMEM block
of every client's leaf and emits the matching block of every cluster's;
the (D, g) weights sit in SMEM and are read as scalars.

Block layout (``_tiling.stream_rows``): each leaf is viewed as (C, n, l)
without moving data and tiled as (C, tn, tl) -> (D, tn, tl) over a
(cdiv(n, tn), cdiv(l, tl)) grid.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .._tiling import stream_rows

__all__ = ["cluster_agg_kernel", "cluster_agg_pallas"]


def cluster_agg_kernel(wt_ref, w_ref, out_ref):
    d, g = wt_ref.shape
    for k in range(d):
        acc = None
        for j in range(g):
            t = wt_ref[k, j] * w_ref[k * g + j].astype(jnp.float32)
            acc = t if acc is None else acc + t
        out_ref[k] = acc.astype(out_ref.dtype)


def cluster_agg_pallas(
    w: jax.Array,
    weights: jax.Array,
    num_clusters: int,
    tile_m: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """w: (C, ...); weights: (C,) m^ ratios; clusters are contiguous C/D groups."""
    c = w.shape[0]
    d = num_clusters
    if c % d:
        raise ValueError("C must be divisible by num_clusters")
    wt = weights.astype(jnp.float32).reshape(d, c // d)
    return stream_rows(
        cluster_agg_kernel, w, (wt,), d,
        work_rows=2, lane_tile=tile_m, interpret=interpret,
    )
