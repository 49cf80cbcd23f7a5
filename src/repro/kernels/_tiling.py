"""Layout-preserving leaf views and VMEM block shapes for the streaming kernels.

``fused_transition``, ``gossip_mix`` and ``cluster_agg`` mix the leading
(client/cluster) rows of a stacked ``(rows, ...)`` parameter leaf;
``fused_sgd`` updates a leaf elementwise.  A TPU stores such a leaf in its
native tiled layout: the minor two dims in (sublane, 128-lane) tiles.
Flattening it to ``(rows, M)`` makes XLA relayout the whole leaf, padding
the few ``rows`` out to the sublane tile; at granite-8b widths that relayout
stalled the round step's compile for minutes.  So the kernels never
flatten.  A leaf ``(rows, *mid, n, l)`` is viewed as ``(rows, prod(mid) * n,
l)``, a bitcast, and streamed in ``(rows, tn, tl)`` blocks over a ``cdiv``
grid.  The edge blocks may be partial: every map here is pointwise over the
``(n, l)`` positions, so the padding of an edge block never reaches a real
output position.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_shape", "compiler_params", "mix", "stream_rows"]

# VMEM a block should use: double-buffered operand blocks plus the kernel's
# f32 working rows.  Half the 16 MiB scoped-VMEM default leaves the compiler
# room.  A block whose smallest legal shape is larger (a fleet of hundreds of
# clients puts hundreds of (sublane, 128) tiles in every block) gets a scoped
# limit of twice its size instead, up to _VMEM_LIMIT_MAX (v5e and later hold
# 128 MiB of VMEM); beyond that the leaf cannot be streamed and we refuse it.
_VMEM_BUDGET = 8 << 20
_VMEM_SCOPED_DEFAULT = 16 << 20
_VMEM_LIMIT_MAX = 96 << 20

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_shape(n: int, l: int, *, lane_tile: int, bytes_per_pos: int,
                itemsize: int, max_rows: int | None = None) -> tuple[int, int, int]:
    """``(tn, tl, vmem_bytes)`` for an ``(n, l)`` plane.

    ``bytes_per_pos`` is the VMEM one ``(n, l)`` position of a block costs;
    ``vmem_bytes`` is what the chosen block costs, counted on its padded
    (sublane, 128-lane) tiles.  ``tl`` starts at ``l`` when it fits in
    ``lane_tile`` (a full dim is always a legal block) and ``lane_tile``
    otherwise, a multiple of 128 on TPU.  While one sublane tile of rows is
    over the budget, ``tl`` is halved, down to 128 lanes.  ``tn`` then fills
    the budget in whole sublane tiles, at most ``max_rows`` of them when
    given.  Raises when even the smallest block is over ``_VMEM_LIMIT_MAX / 2``.
    """
    sublane = 8 * max(1, 4 // itemsize)
    cost = lambda tn, tl: (bytes_per_pos * _round_up(tn, sublane)  # noqa: E731
                           * _round_up(tl, 128))
    tn_min = min(n, sublane)
    tl = l if l <= lane_tile else lane_tile
    while tl > 128 and cost(tn_min, tl) > _VMEM_BUDGET:
        tl = max(128, tl // 2 // 128 * 128)
    if 2 * cost(tn_min, tl) > _VMEM_LIMIT_MAX:
        raise ValueError(
            f"the smallest ({tn_min}, {tl}) block of an ({n}, {l}) plane needs "
            f"{cost(tn_min, tl) / 2**20:.1f} MiB of VMEM at {bytes_per_pos} B "
            f"per position; at most {_VMEM_LIMIT_MAX / 2**21:.0f} MiB can be streamed")
    tn = _VMEM_BUDGET // (bytes_per_pos * _round_up(tl, 128)) // sublane * sublane
    tn = max(tn_min, tn if max_rows is None else min(tn, max_rows))
    if tn >= n:
        tn = n
    return tn, tl, cost(tn, tl)


def mix(coef_ref, xs, out_idx, transpose=False):
    """``sum_j coef[i, j] * xs[j]`` for one output row ``i`` (``coef[j, i]``
    with ``transpose``)."""
    acc = None
    for j, x in enumerate(xs):
        c = coef_ref[j, out_idx] if transpose else coef_ref[out_idx, j]
        acc = c * x if acc is None else acc + c * x
    return acc


def compiler_params(vmem_bytes: int):
    """Raise the scoped-VMEM limit for a block over the budget."""
    if vmem_bytes <= _VMEM_BUDGET:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(_VMEM_SCOPED_DEFAULT, 2 * vmem_bytes))


def stream_rows(kernel, w: jax.Array, factors: tuple, out_rows: int, *,
                work_rows: int, lane_tile: int, interpret: bool,
                factors_in_vmem: bool = False) -> jax.Array:
    """Run ``kernel(*factor_refs, w_ref, out_ref)`` over a stacked leaf.

    ``w`` is ``(rows, ...)``; the result is ``(out_rows, ...)`` in ``w``'s
    dtype.  ``factors`` are the small f32 mixing matrices, passed whole: in
    SMEM to be read as scalars, or in VMEM (``factors_in_vmem``) as MXU
    operands, in which case the kernel walks a block one sublane row at a
    time and a block holds at most one sublane tile of rows.  ``work_rows``
    counts the f32 rows the kernel keeps live per position (it sizes the
    blocks).  A shape-preserving map writes in place: the output aliases
    ``w``, so under donation the step needs no second copy of the leaf.
    """
    rows = w.shape[0]
    # (rows, *mid, l) -> (rows, n, l): a bitcast, the lane dim stays last
    x = w.reshape(rows, -1, w.shape[-1] if w.ndim > 1 else 1)
    _, n, l = x.shape
    isz = x.dtype.itemsize
    tn, tl, vmem_bytes = block_shape(
        n, l, lane_tile=lane_tile, itemsize=isz,
        bytes_per_pos=2 * (rows + out_rows) * isz + 4 * work_rows,
        max_rows=8 * max(1, 4 // isz) if factors_in_vmem else None,
    )
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, tn), pl.cdiv(l, tl)),
        in_specs=[_VMEM if factors_in_vmem else _SMEM] * len(factors) + [
            pl.BlockSpec((rows, tn, tl), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((out_rows, tn, tl), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((out_rows, n, l), x.dtype),
        input_output_aliases={len(factors): 0} if out_rows == rows else {},
        compiler_params=compiler_params(vmem_bytes),
        interpret=interpret,
    )(*factors, x)
    return out.reshape((out_rows,) + w.shape[1:])
