"""Pallas TPU kernel: fused Lemma-1 transition  W <- W @ (V P^alpha B).

The paper's inter-cluster aggregation event factors into three stages on the
stacked client models W (C, ...):

    Y  = V^T W        intra-cluster weighted reduce      (D, ...)
    Y' = (P^T)^a Y    alpha gossip rounds on clusters    (D, ...)
    W' = B^T Y'       broadcast back to cluster members  (C, ...)

Running these as separate kernels (``cluster_agg`` then ``gossip_mix`` then
an einsum) writes and re-reads the (D, ...) intermediate from HBM twice.
This kernel fuses all three on one VMEM block of every client's leaf, so
HBM traffic is exactly one read + one write of W (in place) — the bandwidth
lower bound for the transition.  The body is chosen at trace time from the
C·D terms of a stage:

* a handful (``C * D <= VPU_MAX_TERMS``, e.g. 4 clients in 2 clusters): the
  factors ``V^T`` (D, C), ``P`` (D, D) and ``B^T`` (C, D) sit in SMEM and
  the stages are scalar-times-block FMAs on the VPU in f32;
* a fleet (hundreds of clients): unrolled FMAs would grow with C·D, so the
  factors sit in VMEM and each stage is an f32 MXU contraction over the
  client rows, one sublane row of the block at a time.

With ``alpha == 0`` the mixing stage is skipped and the kernel computes the
intra-cluster event ``W @ (V B)`` instead.

Block layout (``_tiling.stream_rows``): each leaf is viewed as (C, n, l)
without moving data and tiled as (C, tn, tl) over a (cdiv(n, tn),
cdiv(l, tl)) grid — embarrassingly parallel over model positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .._tiling import mix, stream_rows

__all__ = ["fused_transition_kernel", "fused_transition_mxu_kernel",
           "fused_transition_pallas"]


# Most C·D terms a stage may unroll as VPU FMAs, which keeps the unrolled
# body to a few hundred vector ops.  The one-chip smoke (4 clients, 2
# clusters) unrolls 8; fleets of hundreds of clients take the MXU body.
VPU_MAX_TERMS = 64


def fused_transition_kernel(vt_ref, p_ref, bt_ref, w_ref, out_ref, *, alpha: int):
    """VPU body: factors are SMEM scalars, every stage an unrolled FMA."""
    d, c = vt_ref.shape
    # Y = V^T W, one client row at a time: D accumulators + one live row
    y = [None] * d
    for i in range(c):
        w = w_ref[i].astype(jnp.float32)        # (tn, tl)
        for k in range(d):
            t = vt_ref[k, i] * w
            y[k] = t if y[k] is None else y[k] + t
    for _ in range(alpha):
        # column convention: new[k] = sum_j p[j, k] y[j]  (P^T y)
        y = [mix(p_ref, y, k, transpose=True) for k in range(d)]
    for i in range(c):
        out_ref[i] = mix(bt_ref, y, i).astype(out_ref.dtype)


def fused_transition_mxu_kernel(vt_ref, p_ref, bt_ref, w_ref, out_ref, *,
                                alpha: int):
    """MXU body: factors are VMEM matrices; a block holds at most one
    sublane tile of rows, walked one (C, tl) row at a time."""
    dot = functools.partial(jax.lax.dot_general, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    vt, p, bt = vt_ref[...], p_ref[...], bt_ref[...]
    for r in range(w_ref.shape[1]):
        y = dot(vt, w_ref[:, r, :].astype(jnp.float32), (((1,), (0,)), ((), ())))
        for _ in range(alpha):
            y = dot(p, y, (((0,), (0,)), ((), ())))     # P^T y
        out_ref[:, r, :] = dot(bt, y, (((1,), (0,)), ((), ()))).astype(out_ref.dtype)


def fused_transition_pallas(
    w: jax.Array,
    vt: jax.Array,
    p: jax.Array,
    bt: jax.Array,
    alpha: int = 1,
    tile_m: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """w: (C, ...) stacked leaf; vt: (D, C) = V^T; p: (D, D); bt: (C, D) = B^T.

    ``tile_m`` caps the lane width of a block (a multiple of 128 on TPU)."""
    c = w.shape[0]
    d = p.shape[0]
    if vt.shape != (d, c) or bt.shape != (c, d):
        raise ValueError(f"factor shapes {vt.shape}/{bt.shape} inconsistent with "
                         f"C={c}, D={d}")
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    mxu = c * d > VPU_MAX_TERMS
    return stream_rows(
        functools.partial(fused_transition_mxu_kernel if mxu else fused_transition_kernel,
                          alpha=alpha),
        w, (f32(vt), f32(p), f32(bt)), c,
        # live f32 rows: the MXU body holds one (C, tl) row in and out, the
        # VPU body D accumulators and two client rows
        work_rows=2 * c + d if mxu else d + 2,
        lane_tile=tile_m, interpret=interpret, factors_in_vmem=mxu,
    )
