"""Sequence-sharded decode attention with log-sum-exp merging.

Decode shapes keep KV caches of up to 524,288 tokens; a single chip cannot
hold (or stream) them, so the cache sequence dimension is sharded across mesh
axes (``model`` for decode_32k; ``data`` x ``model`` — plus ``pod`` multi-pod
— for long_500k).  Each shard:

  1. writes the new token's K/V into its slot *iff* it owns the ring-buffer
     position (branch-free masked dynamic-update-slice);
  2. computes partial attention stats (acc, l, m) over its local chunk;
  3. merges across shards with the online-softmax identity:
         m* = max_shards m ;  l* = sum l * exp(m - m*) ;
         acc* = sum acc * exp(m - m*) ;  out = acc* / l*
     via ``lax.pmax`` / ``lax.psum`` over the sequence axes.

Query heads are tensor-sharded on ``model``; since the query is a single
token, an all-gather of q over ``model`` (a few KB) is negligible against the
cache traffic it saves.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import decode_attention_stats

__all__ = ["make_decode_impl"]


def _flat_shard_index(axes: tuple[str, ...], sizes: dict[str, int]):
    """Row-major flattened shard id over ``axes`` (static strides)."""
    idx = jnp.zeros((), jnp.int32)
    stride = 1
    for a in reversed(axes):
        idx = idx + jax.lax.axis_index(a) * stride
        stride *= sizes[a]
    return idx


def make_decode_impl(
    mesh: jax.sharding.Mesh,
    *,
    seq_axes: tuple[str, ...],
    batch_axes: tuple[str, ...],
    gather_heads: bool,
    model_axis: str = "model",
):
    """Build a decode-attention impl for ``CausalLM(decode_impl=...)``.

    Contract (see models.transformer._attention):
        fn(q1, k_cache, v_cache, slot_pos, q_pos, k_new, v_new,
           *, window, logit_cap) -> (out, new_k, new_v, new_pos)
    with q1 (B, Hq, hd); caches (B, Sc, Hkv, hd); slot_pos (Sc,).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_seq = math.prod(sizes[a] for a in seq_axes)
    batch_spec = batch_axes[0] if len(batch_axes) == 1 else (tuple(batch_axes) or None)
    seq_spec = seq_axes[0] if len(seq_axes) == 1 else (tuple(seq_axes) or None)
    q_head_spec = model_axis if gather_heads else None

    def impl(q1, k_cache, v_cache, slot_pos, q_pos, k_new, v_new, *, window, logit_cap):
        sc = k_cache.shape[1]
        if sc % n_seq:
            raise ValueError(f"cache length {sc} not divisible by {n_seq} seq shards")

        def local(q, kc, vc, sp, qp, kn, vn):
            if gather_heads:
                q = jax.lax.all_gather(q, model_axis, axis=1, tiled=True)
            sc_l = kc.shape[1]
            shard = _flat_shard_index(seq_axes, sizes)
            slot = (qp % sc).astype(jnp.int32)
            local_slot = slot - shard * sc_l
            in_range = (local_slot >= 0) & (local_slot < sc_l)
            safe = jnp.clip(local_slot, 0, sc_l - 1)
            kc_w = jax.lax.dynamic_update_slice(
                kc, kn[:, None].astype(kc.dtype), (0, safe, 0, 0)
            )
            vc_w = jax.lax.dynamic_update_slice(
                vc, vn[:, None].astype(vc.dtype), (0, safe, 0, 0)
            )
            sp_w = jax.lax.dynamic_update_slice(sp, qp[None].astype(jnp.int32), (safe,))
            kc = jnp.where(in_range, kc_w, kc)
            vc = jnp.where(in_range, vc_w, vc)
            sp = jnp.where(in_range, sp_w, sp)

            acc, l, m = decode_attention_stats(
                q, kc, vc, sp, qp, window=window, logit_cap=logit_cap
            )
            m_g = jax.lax.pmax(m, seq_axes)
            corr = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * corr, seq_axes)
            acc_g = jax.lax.psum(acc * corr[..., None], seq_axes)
            out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(q.dtype)
            return out, kc, vc, sp

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(batch_spec, q_head_spec, None),          # q1
                P(batch_spec, seq_spec, None, None),       # k_cache
                P(batch_spec, seq_spec, None, None),       # v_cache
                P(seq_spec),                               # slot_pos
                P(),                                       # q_pos
                P(batch_spec, None, None),                 # k_new
                P(batch_spec, None, None),                 # v_new
            ),
            out_specs=(
                P(batch_spec, None, None),                 # out (full heads)
                P(batch_spec, seq_spec, None, None),
                P(batch_spec, seq_spec, None, None),
                P(seq_spec),
            ),
            check_vma=False,
        )(q1, k_cache, v_cache, slot_pos, q_pos, k_new, v_new)

    return impl
