"""Mixture-of-Experts layer: top-k router + sort-based capacity dispatch.

TPU-native adaptation: instead of the dense one-hot dispatch einsum (whose
cost is quadratic in sequence length), tokens are routed by sorting the
(token, expert) assignment list by expert id and scattering into fixed
``(num_experts, capacity)`` buffers — O(T log T) bookkeeping and expert GEMM
FLOPs proportional to *active* parameters, which keeps the roofline compute
term honest for grok-1 / mixtral / jamba.  Over-capacity tokens are dropped
(standard capacity-factor semantics); the router aux loss balances load.

Sharding note (§Perf iteration 1): dispatch is **per-example** (vmapped over
the batch dim) whenever S > 1.  A single global sort over the flattened
(B*S) token axis forces XLA to reduce a *replicated* (E, capacity, d) buffer
across the batch-sharded mesh axes — measured at ~4.7 TB/device of all-reduce
for mixtral prefill_32k.  Per-example dispatch keeps every sort/scatter local
to the data shard that owns the example (verified: collective term 94 s ->
~2 s in the dry-run).  Decode steps (S == 1) keep the flat path, where the
token axis is the batch axis itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding.context import constrain_batch, current as sharding_ctx

__all__ = ["moe_mlp", "init_moe_params", "router_topk"]


def router_topk(x: jax.Array, w_router: jax.Array, k: int):
    """Returns (expert_ids (T,k), combine_weights (T,k), aux_loss, probs)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e.
    e = w_router.shape[1]
    assign = jnp.zeros_like(probs).at[jnp.arange(ids.shape[0])[:, None], ids].add(1.0)
    f_e = assign.mean(axis=0) / k
    p_e = probs.mean(axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return ids, weights, aux, probs


def _dispatch_compute_combine(xt, params, k: int, capacity: int):
    """Sort-based dispatch for one flat token set. xt: (T, d) -> ((T, d), aux).

    Gather-only data movement: GSPMD partitions (batched) gathers along the
    sharded batch dim but falls back to replicate-and-all-reduce for the
    equivalent (T, d) scatters (§Perf iteration 3).  Only O(T*k) int32
    bookkeeping uses a scatter."""
    t, d = xt.shape
    e = params["w_router"].shape[1]
    ids, weights, aux, _ = router_topk(xt, params["w_router"], k)

    flat_e = ids.reshape(t * k)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    se, stok = flat_e[order], flat_tok[order]

    counts = jnp.bincount(se, length=e)
    offsets = jnp.cumsum(counts) - counts                 # start of each expert run
    pos_in_expert = jnp.arange(t * k, dtype=jnp.int32) - offsets[se]
    keep_sorted = pos_in_expert < capacity

    # ---- gather-based dispatch: buf[e, c] = xt[token of expert e's slot c]
    slot_positions = offsets[:, None] + jnp.arange(capacity, dtype=jnp.int32)[None, :]
    valid = jnp.arange(capacity, dtype=jnp.int32)[None, :] < jnp.minimum(counts, capacity)[:, None]
    src_tok = stok[jnp.clip(slot_positions, 0, t * k - 1)]           # (E, cap)
    buf = jnp.where(valid[..., None], xt[src_tok], 0)                 # (E, cap, d)

    # ---- expert GEMMs ------------------------------------------------------
    gate = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(xt.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(xt.dtype))
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(xt.dtype) * up
    expert_out = jnp.einsum("ecf,efd->ecd", hidden, params["w_down"].astype(xt.dtype))

    # ---- gather-based combine: slot of assignment (t, k) via inverse perm
    slot_sorted = jnp.where(keep_sorted, se * capacity + pos_in_expert, e * capacity)
    slot_flat = jnp.zeros((t * k,), jnp.int32).at[order].set(slot_sorted)  # tiny int scatter
    keep_flat = slot_flat < e * capacity
    padded = jnp.concatenate(
        [expert_out.reshape(e * capacity, d), jnp.zeros((1, d), xt.dtype)], axis=0
    )
    per_assign = padded[slot_flat].reshape(t, k, d)                   # (T, k, d) gather
    w = (weights * keep_flat.reshape(t, k).astype(jnp.float32)).astype(xt.dtype)
    out = jnp.einsum("tkd,tk->td", per_assign, w)
    return out, aux.astype(jnp.float32)


def moe_mlp(x: jax.Array, params: dict, *, num_experts_per_tok: int, capacity_factor: float):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    b, s, d = x.shape
    e = params["w_router"].shape[1]
    k = num_experts_per_tok

    if s > 1:
        capacity = int(max(1, round(s * k / e * capacity_factor), min(s, 16)))

        def batched(xb):
            out, aux = jax.vmap(
                lambda xe: _dispatch_compute_combine(xe, params, k, capacity)
            )(xb)
            return out, aux.mean()

        # Partial-manual shard_map over the batch axes (model axis stays in
        # auto/propagation mode): GSPMD's scatter/gather partitioning
        # otherwise replicates the dispatch across the data axis — measured
        # 16x redundant expert FLOPs + 3.9 TB/device of collectives on
        # mixtral prefill_32k (§Perf iterations 1-3).  Manual batch sharding
        # makes every sort/gather shard-local by construction.
        ctx = sharding_ctx()
        if ctx is not None and ctx.get("moe_shard_map", True):
            mesh = ctx["mesh"]
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            axes = [a for a in ctx["batch_axes"] if sizes.get(a, 1) > 1]
            div = 1
            for a in axes:
                div *= sizes[a]
            if axes and b % div == 0:
                bspec = tuple(axes) if len(axes) > 1 else axes[0]

                def local_fn(xb):
                    out, aux = batched(xb)
                    return out, jax.lax.pmean(aux, tuple(axes))

                return jax.shard_map(
                    local_fn, mesh=mesh,
                    in_specs=(jax.sharding.PartitionSpec(bspec, None, None),),
                    out_specs=(jax.sharding.PartitionSpec(bspec, None, None),
                               jax.sharding.PartitionSpec()),
                    axis_names=frozenset(axes), check_vma=False,
                )(x)
        return batched(x)

    # decode path (S == 1): the token axis IS the batch axis; flat dispatch.
    t = b * s
    capacity = int(max(1, round(t * k / e * capacity_factor), min(t, 16)))
    out, aux = _dispatch_compute_combine(x.reshape(t, d), params, k, capacity)
    return out.reshape(b, s, d), aux


def init_moe_params(rng, d_model: int, d_ff: int, num_experts: int, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s_in, s_ff = d_model ** -0.5, d_ff ** -0.5
    normal = jax.random.normal
    return {
        "w_router": (normal(k1, (d_model, num_experts), jnp.float32) * s_in).astype(jnp.float32),
        "w_gate": (normal(k2, (num_experts, d_model, d_ff), jnp.float32) * s_in).astype(dtype),
        "w_up": (normal(k3, (num_experts, d_model, d_ff), jnp.float32) * s_in).astype(dtype),
        "w_down": (normal(k4, (num_experts, d_ff, d_model), jnp.float32) * s_ff).astype(dtype),
    }
