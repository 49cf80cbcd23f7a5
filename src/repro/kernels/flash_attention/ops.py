"""Causal GQA flash attention on the TPU, differentiable (pallas | ref).

The Pallas path is the splash attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``): a forward kernel
and separate dq and dkv backward kernels joined by a ``custom_vjp``.  Each
keeps its score and probability tiles in VMEM, skips the blocks its
block-sparse mask leaves empty (the upper triangle under ``CausalMask``,
and the blocks outside the band under ``LocalMask``).  Its q.k product and
the backward's products take the operands' dtype (bf16 in training) into
the MXU with float32 accumulation; the forward's p.v takes p and v in
float32; the running max, normaliser and accumulators are float32.

GQA runs as MQA per KV head: the ``G = Hq / Hkv`` query heads of a group
are the kernel's heads, and the kernel is vmapped over batch and KV heads,
so K and V are never repeated.  ``hd ** -0.5`` is folded into q before the
kernel, which applies the logit softcap to the scaled scores, as the XLA
path does.  A head dim that is not a multiple of 128 is zero-padded to the
MXU lane width (zeros change no score).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from .ref import flash_attention_ref

__all__ = ["block_size", "flash_attention"]

# q and kv tile, largest first.  On a v5e at S=1024 (32 query and 8 KV heads
# of 128) a forward, rematerialised forward and backward take about as long
# at 512 as at 1024 (one block, no causal skip), and about 60% longer at 256.
BLOCKS = (512, 256, 128)


def block_size(s: int) -> Optional[int]:
    """The kernel's q and kv tile for sequence length ``s``: the largest of
    ``BLOCKS`` that divides it, or None where none does."""
    return next((b for b in BLOCKS if s % b == 0), None)


def _kernel(s: int, heads: int, window: Optional[int], logit_cap: Optional[float],
            interpret: bool):
    """The MQA splash kernel over ``heads`` query heads of one KV head.  Built
    in each trace: its block tables become that trace's constants (the numpy
    mask processing behind them is cached by splash)."""
    block = block_size(s)
    if block is None:
        raise ValueError(f"S={s} is not a multiple of any flash block in {BLOCKS}")
    if window is None:
        mask = splash.CausalMask((s, s))
    else:  # q attends to k in [q - window + 1, q]
        mask = splash.LocalMask((s, s), (window - 1, 0), 0)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block,
    )
    return splash.make_splash_mqa_single_device(
        splash.MultiHeadMask([mask] * heads), block_sizes=sizes,
        attn_logits_soft_cap=logit_cap, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("window", "logit_cap", "impl", "interpret"))
def flash_attention(
    q, k, v,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    impl: str = "pallas",
    interpret: bool = False,
):
    """q: (B, S, Hq, hd); k, v: (B, S, Hkv, hd).  Returns (B, S, Hq, hd):
    causal attention of position i over positions [i - window + 1, i]."""
    if impl == "ref":
        return flash_attention_ref(q, k, v, window=window, logit_cap=logit_cap)
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    q = q * jnp.asarray(hd ** -0.5, q.dtype)
    pad = (-hd) % 128  # MXU lane alignment
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad))) for x in (q, k, v))
    d = hd + pad
    kernel = _kernel(s, g, window, logit_cap, interpret)
    qh = q.reshape(b, s, hkv, g, d).transpose(0, 2, 3, 1, 4).reshape(b * hkv, g, s, d)
    kh, vh = (x.transpose(0, 2, 1, 3).reshape(b * hkv, s, d) for x in (k, v))
    out = jax.vmap(kernel)(qh, kh, vh)  # (B * Hkv, G, S, d)
    out = out.reshape(b, hkv, g, s, d).transpose(0, 3, 1, 2, 4).reshape(b, s, hq, d)
    return out[..., :hd]
