"""Kernel operations and bytes, counted from shapes alone.

The counters take the leaf shapes of the stacked ``(C, ...)`` client trees
and count the algorithm's HBM traffic: each operand read once and each
output written once.  A multiply-add counts 2.  A model's own FLOPs are
counted in its file under ``bench/models/``.
"""
from __future__ import annotations

import math


def leaf_elements(shapes) -> int:
    """Elements of every leaf, each shape given with its leading client axis."""
    return sum(math.prod(s) for s in shapes)


def transition_cost(shapes, itemsize: int, clients: int, clusters: int,
                    alpha: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one fused transition ``W <- W @ (V P^alpha B)``
    over stacked leaves of the given shapes.

    Per model position: V^T W and B^T Y are C*D multiply-adds each, and
    each of the ``alpha`` gossip rounds is D*D; W is read once and written
    once in place.
    """
    positions = leaf_elements(shapes) / clients
    flops = positions * (4.0 * clients * clusters + 2.0 * alpha * clusters * clusters)
    bytes_ = positions * 2.0 * clients * itemsize
    return flops, bytes_


def sgd_cost(shapes, itemsize: int, grad_itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one fused SGD pass ``w <- w - lr * g`` over every
    stacked leaf: read w and g, write w in place."""
    n = leaf_elements(shapes)
    return 2.0 * n, n * (2.0 * itemsize + grad_itemsize)


def roofline_share(flops: float, bytes_: float, seconds: float, peaks: dict) -> float:
    """Least time the chip could take over the time measured, in percent."""
    least = max(flops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
