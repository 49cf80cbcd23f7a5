"""Pallas TPU kernel: fused SGD update + eq-(19) normalized client update.

Two elementwise streaming kernels (HBM-bandwidth-bound; fusing the dtype
casts, scale and subtraction into one pass avoids XLA materializing f32
intermediates for bf16 parameters):

* ``sgd_update``:        w <- w - lr * g        (in place: out aliases w)
* ``normalized_update``: delta <- (w_final - w_start) * inv_theta   (eq. 19)

Block layout: an array of any rank is viewed as 2-D (n, l) without moving
data — its last dim stays the lane dim, as the TPU lays it out — and tiled
as (tn, tl) VMEM blocks over a (cdiv(n, tn), cdiv(l, tl)) grid whose edge
blocks may be partial (see ``_tiling``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._tiling import block_shape, compiler_params

__all__ = ["sgd_update_pallas", "normalized_update_pallas"]


def _sgd_kernel(w_ref, g_ref, out_ref, *, lr: float):
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    out_ref[...] = (w - lr * g).astype(out_ref.dtype)


def _norm_update_kernel(wf_ref, w0_ref, out_ref, *, inv_theta: float):
    wf = wf_ref[...].astype(jnp.float32)
    w0 = w0_ref[...].astype(jnp.float32)
    out_ref[...] = ((wf - w0) * inv_theta).astype(out_ref.dtype)


def _tiled_call(kernel, a: jax.Array, b: jax.Array, tile_m: int, interpret: bool,
                in_place: bool):
    shape = a.shape
    l = shape[-1] if shape else 1
    a2, b2 = a.reshape(-1, l), b.reshape(-1, l)
    n = a2.shape[0]
    isz = max(a2.dtype.itemsize, b2.dtype.itemsize)
    # two operands and one output, double-buffered, plus three f32 rows
    tn, tl, vmem_bytes = block_shape(n, l, lane_tile=tile_m, itemsize=isz,
                                     bytes_per_pos=6 * isz + 12)
    spec = pl.BlockSpec((tn, tl), lambda i, j: (i, j))
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, tn), pl.cdiv(l, tl)),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, l), a.dtype),
        input_output_aliases={0: 0} if in_place else {},
        compiler_params=compiler_params(vmem_bytes),
        interpret=interpret,
    )(a2, b2)
    return out.reshape(shape)


def sgd_update_pallas(w, g, lr: float, tile_m: int = 1024, interpret: bool = False):
    return _tiled_call(functools.partial(_sgd_kernel, lr=lr), w, g, tile_m,
                       interpret, in_place=True)


def normalized_update_pallas(w_final, w_start, inv_theta: float, tile_m: int = 1024, interpret: bool = False):
    return _tiled_call(
        functools.partial(_norm_update_kernel, inv_theta=inv_theta),
        w_final, w_start, tile_m, interpret, in_place=False,
    )
