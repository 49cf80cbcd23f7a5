"""Plain reference of granite-8b (IBM Granite Code 8B, arXiv:2405.04324).

A llama-style decoder written out in ``jax.numpy``: token embedding,
per layer RMSNorm -> grouped-query attention with rotary embeddings (the
half-split rotation) -> residual -> RMSNorm -> SwiGLU MLP -> residual, a
final RMSNorm and an untied output head; mean next-token cross-entropy.
Every matrix product runs in float32 at ``highest`` precision from the
stored weights.  Imports nothing of the program under test.

``init`` makes the weights from a key in one jitted call, in the storage
dtype and in the layout the program takes them (layers stacked on a
leading axis under ``blocks/pos0``).

``low=True`` is the control: every matrix-product operand is rounded to
float8 (e4m3) with a per-tensor scale, the precision step below the
configuration's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def leaf_shapes(cfg: dict) -> dict:
    d, f, n = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    v = -(-cfg["vocab_size"] // 256) * 256  # rows held: the vocabulary padded to 256
    q, kv = cfg["num_heads"] * cfg["head_dim"], cfg["num_kv_heads"] * cfg["head_dim"]
    return {
        "embed": (v, d),
        "blocks": {"pos0": {
            "ln_mix": (n, d),
            "attn": {"wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv), "wo": (n, q, d)},
            "ln_ffn": (n, d),
            "ffn": {"w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)},
        }},
        "ln_final": (d,),
        "head": (d, v),
    }


def init(key, cfg: dict):
    """Normal weights: embedding std 0.02, each projection std d_in^-0.5;
    norm scales 1.  One key per leaf, folded in by the leaf's index."""
    dt = jnp.dtype(cfg["dtype"])
    shapes = leaf_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        if "ln_" in name:
            leaves.append(jnp.ones(shape, dt))
            continue
        std = 0.02 if name == "['embed']" else shape[-2] ** -0.5
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
        leaves.append(w.astype(dt))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale; gradients pass through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, low):
    if low:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def logits(params, tokens, cfg: dict, low: bool = False):
    """tokens (B, S) int -> logits (B, S, vocab) in float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    eps, hd = cfg["norm_eps"], cfg["head_dim"]
    hq, hkv = cfg["num_heads"], cfg["num_kv_heads"]
    x = p["embed"][tokens]
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    blk = p["blocks"]["pos0"]
    for layer in range(cfg["num_layers"]):
        lp = jax.tree.map(lambda a: a[layer], blk)
        h = _rms(x, lp["ln_mix"], eps)
        q = _mm("bsd,df->bsf", h, lp["attn"]["wq"], low).reshape(b, s, hq, hd)
        k = _mm("bsd,df->bsf", h, lp["attn"]["wk"], low).reshape(b, s, hkv, hd)
        v = _mm("bsd,df->bsf", h, lp["attn"]["wv"], low).reshape(b, s, hkv, hd)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        q = q.reshape(b, s, hkv, hq // hkv, hd)
        scores = _mm("bqhgd,bkhd->bhgqk", q, k, low) * hd ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1)
        o = _mm("bhgqk,bkhd->bqhgd", attn, v, low).reshape(b, s, hq * hd)
        x = x + _mm("bsf,fd->bsd", o, lp["attn"]["wo"], low)
        h = _rms(x, lp["ln_ffn"], eps)
        gate = _mm("bsd,df->bsf", h, lp["ffn"]["w_gate"], low)
        up = _mm("bsd,df->bsf", h, lp["ffn"]["w_up"], low)
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lp["ffn"]["w_down"], low)
    x = _rms(x, p["ln_final"], eps)
    return _mm("bsd,dv->bsv", x, p["head"], low)[..., : cfg["vocab_size"]]


def loss(params, batch, cfg: dict, low: bool = False):
    """Mean next-token cross-entropy over every position of the batch."""
    lg = logits(params, batch["tokens"], cfg, low)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1))


def storage_dtype(cfg: dict, low: bool = False):
    return jnp.dtype(cfg["dtype"])
