"""Pallas TPU kernel: inter-cluster gossip mixing  Y <- Y @ P  (eq. 4).

Stacked cluster models ``Y`` are a (D, ...) leaf (D = #edge servers).  One
gossip round multiplies by the D x D mixing matrix ``P`` on the cluster
axis.  This is purely HBM-bandwidth-bound (about D flops per byte), so the
kernel streams VMEM blocks of every cluster's leaf and keeps the whole
(tiny) P in SMEM; ``alpha`` rounds reuse the streamed block alpha times
before writing it back in place — raising arithmetic intensity by alpha
versus alpha separate passes (the XLA baseline).

Block layout (``_tiling.stream_rows``): each leaf is viewed as (D, n, l)
without moving data and tiled as (D, tn, tl) over a (cdiv(n, tn),
cdiv(l, tl)) grid — embarrassingly parallel over model positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .._tiling import mix, stream_rows

__all__ = ["gossip_mix_kernel", "gossip_mix_pallas"]


def gossip_mix_kernel(p_ref, y_ref, out_ref, *, alpha: int):
    d = p_ref.shape[0]
    y = [y_ref[k].astype(jnp.float32) for k in range(d)]     # D x (tn, tl)
    # alpha gossip rounds on the VMEM-resident block: new[k] = sum_j p[j,k] y[j]
    for _ in range(alpha):
        y = [mix(p_ref, y, k, transpose=True) for k in range(d)]
    for k in range(d):
        out_ref[k] = y[k].astype(out_ref.dtype)


def gossip_mix_pallas(
    y: jax.Array,
    p: jax.Array,
    alpha: int = 1,
    tile_m: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """y: (D, ...) stacked leaf; p: (D, D) with column convention (Y @ P^alpha).

    ``tile_m`` caps the lane width of a block (a multiple of 128 on TPU)."""
    d = y.shape[0]
    return stream_rows(
        functools.partial(gossip_mix_kernel, alpha=alpha),
        y, (p.astype(jnp.float32),), d,
        work_rows=2 * d, lane_tile=tile_m, interpret=interpret,
    )
