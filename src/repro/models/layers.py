"""Core neural layers: RMSNorm, RoPE, GQA attention (blocked + decode), MLP,
and the training loss's softmax cross-entropy.

Attention comes in two executions:

* ``blocked_causal_attention`` — flash-structured online-softmax over KV
  chunks using two nested ``lax.scan``s (O(chunk^2) memory, O(S^2) compute).
  Training and prefill take it off the TPU, for sequence lengths the Pallas
  kernel's blocks do not tile, and where the q chunks shard over the model
  axis.  On a TPU they otherwise take the differentiable Pallas kernel in
  ``repro.kernels.flash_attention`` (``models.transformer._flash_kernel``),
  which keeps its tiles in VMEM and skips the blocks causality masks.
* ``decode_attention`` — one query token against a KV cache, with the
  (numerator, denominator, max) stats exposed separately so the distribution
  layer can LSE-merge partial results across a sequence-sharded cache.

Supports GQA (grouped KV heads), sliding windows, attention logit softcaps,
and ring-buffer caches via per-slot absolute positions.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "rms_norm",
    "rope",
    "blocked_causal_attention",
    "decode_attention",
    "decode_attention_stats",
    "finalize_decode_stats",
    "gated_mlp",
    "dense",
    "init_dense",
    "softcap",
    "softmax_cross_entropy",
]

NEG_INF = -1e30


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def softmax_cross_entropy(
    logits: jax.Array, labels: jax.Array, *, vocab: int, softcap: Optional[float] = None
) -> jax.Array:
    """Per-position NLL of ``labels`` under ``softmax(softcap(logits))``.

    logits: (..., V) with V >= ``vocab``; the columns at or above ``vocab``
    are padding and take no probability.  labels: (...) int.  The logits are
    upcast to float32 only inside the reductions, and the backward writes the
    gradient straight in the logits' dtype, so no float32 copy of the
    vocabulary-wide tensor is ever stored.  ``labels`` gets no gradient.
    """
    return _cross_entropy(logits, labels, vocab, softcap)


def _capped_logits(logits, vocab, cap):
    """float32 logits after the softcap, with the padding columns at -inf;
    and tanh(logits / cap), or None without a softcap."""
    x = logits.astype(jnp.float32)
    t = None
    if cap is not None:
        t = jnp.tanh(x / cap)
        x = cap * t
    if vocab < x.shape[-1]:
        x = jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) < vocab,
                      x, -jnp.inf)
    return x, t


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _cross_entropy(logits, labels, vocab, cap):
    return _cross_entropy_fwd(logits, labels, vocab, cap)[0]


def _cross_entropy_fwd(logits, labels, vocab, cap):
    x, _ = _capped_logits(logits, vocab, cap)
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1))
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    picked = softcap(picked.astype(jnp.float32), cap)
    return lse - picked, (logits, labels, lse)


def _cross_entropy_bwd(vocab, cap, res, g):
    logits, labels, lse = res
    x, t = _capped_logits(logits, vocab, cap)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    # exp(-inf) = 0 and no label: the padding columns' gradient is exactly 0
    d = jnp.exp(x - lse[..., None]) - (col == labels[..., None]).astype(jnp.float32)
    if t is not None:
        d = d * (1.0 - t * t)
    # Stored once in the logits' dtype: fused into the two gradient matmuls
    # that read it, the exp would be recomputed for every tile of their output.
    return jax.lax.optimization_barrier((d * g[..., None]).astype(logits.dtype)), None


_cross_entropy.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10_000.0) -> jax.Array:
    """Rotary embeddings. x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.arange(half, dtype=jnp.float32) / half
    inv = theta ** (-freqs)  # (half,)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        angles = pos[:, None] * inv[None, :]          # (S, half)
        angles = angles[None, :, None, :]             # (1, S, 1, half)
    else:
        angles = pos[:, :, None] * inv[None, None, :]  # (B, S, half)
        angles = angles[:, :, None, :]                # (B, S, 1, half)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked (flash-structured) causal attention — XLA path.
# ---------------------------------------------------------------------------

def blocked_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    chunk: int = 512,
    positions: Optional[jax.Array] = None,
    shard_chunk: bool = False,
) -> jax.Array:
    """Causal GQA attention with online softmax over KV chunks.

    q: (B, S, Hq, hd);  k, v: (B, S, Hkv, hd);  Hq % Hkv == 0.
    Returns (B, S, Hq, hd).
    """
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    chunk = min(chunk, s)
    if s % chunk:
        raise ValueError(f"seq {s} must be divisible by chunk {chunk}")
    n = s // chunk
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)
    scale = hd ** -0.5

    qb = q.reshape(b, n, chunk, hkv, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(b, n, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n, chunk, hkv, hd).transpose(1, 0, 2, 3, 4)
    pb = positions.reshape(n, chunk)
    if shard_chunk:
        # sequence-parallel attention: q rows are independent in the online
        # softmax, so the q-chunk dim shards over the (otherwise idle) model
        # axis — each device handles chunk/M query rows against full K/V.
        from repro.sharding.context import constrain_dim

        qb = constrain_dim(qb, 2)

    def q_block(carry, inp):
        qi, q_pos = inp  # (B, qc, Hkv, G, hd), (qc,)

        def kv_block(state, kv_inp):
            m, l, acc = state
            ki, vi, k_pos = kv_inp
            scores = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qi.astype(jnp.float32), ki.astype(jnp.float32)
            ) * scale
            scores = softcap(scores, logit_cap)
            mask = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * corr + p.sum(axis=-1)
            # NOTE (§Perf iteration G1, refuted): casting p to bf16 for the
            # PV matmul does NOT reduce HBM traffic here — the f32 p tile is
            # still materialized for the row-sum, so the bf16 copy is pure
            # extra traffic (+8% measured).  The real fix is the Pallas flash
            # kernel, which never spills p to HBM at all.
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vi.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), (kb, vb, pb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, Hkv, G, qc, hd) -> (B, qc, Hkv, G, hd)
        out = out.transpose(0, 3, 1, 2, 4)
        if shard_chunk:
            from repro.sharding.context import constrain_dim

            out = constrain_dim(out, 1)
        return carry, out

    _, outs = jax.lax.scan(q_block, None, (qb, pb))
    # outs: (n, B, qc, Hkv, G, hd)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, hq, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention — one new token vs. a (possibly ring-buffer) KV cache.
# ---------------------------------------------------------------------------

def decode_attention_stats(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    slot_pos: jax.Array,
    q_pos: jax.Array,
    *,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
):
    """Partial attention stats for a single query token.

    q: (B, Hq, hd); k_cache/v_cache: (B, Sc, Hkv, hd); slot_pos: (Sc,) absolute
    position stored in each cache slot (-1 = empty); q_pos: scalar int.

    Returns (acc, l, m): (B, Hq, hd), (B, Hq), (B, Hq) — mergeable across
    shards of the cache via ``finalize_decode_stats`` / LSE merge.
    """
    b, hq, hd = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    scale = hd ** -0.5
    qg = q.reshape(b, hkv, g, hd)
    scores = jnp.einsum(
        "bhgd,bkhd->bhgk", qg.astype(jnp.float32), k_cache.astype(jnp.float32)
    ) * scale
    scores = softcap(scores, logit_cap)
    valid = (slot_pos >= 0) & (slot_pos <= q_pos)
    if window is not None:
        valid &= (q_pos - slot_pos) < window
    scores = jnp.where(valid[None, None, None], scores, NEG_INF)
    m = scores.max(axis=-1)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(valid[None, None, None], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return acc.reshape(b, hq, hd), l.reshape(b, hq), m.reshape(b, hq)


def finalize_decode_stats(acc: jax.Array, l: jax.Array, m: jax.Array, dtype) -> jax.Array:
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(dtype)


def decode_attention(
    q, k_cache, v_cache, slot_pos, q_pos, *, window=None, logit_cap=None
) -> jax.Array:
    acc, l, m = decode_attention_stats(
        q, k_cache, v_cache, slot_pos, q_pos, window=window, logit_cap=logit_cap
    )
    return finalize_decode_stats(acc, l, m, q.dtype)


# ---------------------------------------------------------------------------
# MLP + parameter initialization helpers.
# ---------------------------------------------------------------------------

def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def gated_mlp(x: jax.Array, params: dict) -> jax.Array:
    gate = dense(x, params["w_gate"])
    up = dense(x, params["w_up"])
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return dense(hidden, params["w_down"])


def init_dense(rng, d_in: int, d_out: int, dtype, scale: float | None = None) -> jax.Array:
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)
