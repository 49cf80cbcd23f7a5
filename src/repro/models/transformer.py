"""Unified decoder-only model covering all assigned architecture families.

A model is a stack of ``num_scan_blocks`` homogeneous *scan blocks*; each scan
block contains ``cfg.scan_period`` layers with a fixed kind pattern (attn /
mamba, dense-FFN / MoE-FFN / no-FFN), so the whole stack is one ``lax.scan``
over stacked block parameters — keeping HLO size O(1) in depth for the
512-device dry-run compiles.  Activation checkpointing (``jax.checkpoint``)
wraps the block body when ``cfg.remat``.

Three entry points:
  * ``forward``      — full-sequence logits (the prefill math; ``loss`` takes
                       the head's own-dtype output into
                       ``layers.softmax_cross_entropy`` instead)
  * ``prefill``      — forward + KV/SSM cache construction
  * ``decode_step``  — one token against the cache (ring-buffer aware)

Modality carve-outs (per the brief): pixtral's vision tower and musicgen's
EnCodec codec are stubs — ``frontend_embeds`` replace the first F token
embeddings (VLM) and per-codebook token grids are summed at the embedding
(audio, K output heads).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .. import spans
from ..kernels.flash_attention import block_size, flash_attention
from .config import ArchConfig
from .layers import (
    blocked_causal_attention,
    decode_attention,
    dense,
    gated_mlp,
    init_dense,
    rms_norm,
    rope,
    softcap,
    softmax_cross_entropy,
)
from .mamba import init_mamba_cache, init_mamba_params, mamba_decode_step, mamba_forward
from .moe import init_moe_params, moe_mlp

PyTree = Any

__all__ = ["CausalLM"]


# ---------------------------------------------------------------------------
# Per-layer parameter init
# ---------------------------------------------------------------------------

def _init_attn_params(rng, cfg: ArchConfig) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    dt = cfg.param_dtype
    p = {
        "wq": init_dense(ks[0], d, hq * hd, dt),
        "wk": init_dense(ks[1], d, hkv * hd, dt),
        "wv": init_dense(ks[2], d, hkv * hd, dt),
        "wo": init_dense(ks[3], hq * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    return p


def _init_ffn_params(rng, cfg: ArchConfig, moe: bool) -> dict:
    if moe:
        return init_moe_params(rng, cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.param_dtype)
    ks = jax.random.split(rng, 3)
    dt = cfg.param_dtype
    return {
        "w_gate": init_dense(ks[0], cfg.d_model, cfg.d_ff, dt),
        "w_up": init_dense(ks[1], cfg.d_model, cfg.d_ff, dt),
        "w_down": init_dense(ks[2], cfg.d_ff, cfg.d_model, dt),
    }


def _init_layer_params(rng, cfg: ArchConfig, idx_in_period: int) -> dict:
    kind = cfg.layer_kind(idx_in_period)
    moe = cfg.is_moe_layer(idx_in_period)
    k_mix, k_ffn = jax.random.split(rng)
    dt = cfg.param_dtype
    p: dict = {"ln_mix": jnp.ones((cfg.d_model,), dt)}
    if kind == "attn":
        p["attn"] = _init_attn_params(k_mix, cfg)
    else:
        p["mamba"] = init_mamba_params(k_mix, cfg)
    if cfg.use_post_norm:
        p["ln_mix_post"] = jnp.ones((cfg.d_model,), dt)
    if cfg.d_ff:
        p["ln_ffn"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = _init_ffn_params(k_ffn, cfg, moe)
        if cfg.use_post_norm:
            p["ln_ffn_post"] = jnp.ones((cfg.d_model,), dt)
    return p


# ---------------------------------------------------------------------------
# Per-layer application
# ---------------------------------------------------------------------------

def _flash_kernel(s: int):
    """The Pallas flash-attention kernel for a full-sequence pass, where it
    runs: on a TPU, for a sequence its blocks tile, on a host of one chip.
    The compiler cannot partition a Mosaic kernel, and every program that
    spans chips (the collective round step, the cluster-mesh server, the
    launch layer's steps and their model-axis q chunks) needs a host of
    several.  Otherwise None, and the pass takes ``blocked_causal_attention``."""
    if jax.default_backend() != "tpu" or jax.device_count() != 1 or block_size(s) is None:
        return None
    return flash_attention


def _attention(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    window: Optional[int],
    positions: jax.Array,
    cache: Optional[dict],
    q_pos: Optional[jax.Array],
    return_cache: bool,
    decode_impl=None,
):
    b, s, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, hq, hd)
    k = dense(x, p["wk"], p.get("bk")).reshape(b, s, hkv, hd)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, s, hkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and q_pos is not None:
        # decode: write this token into the (ring) cache, then attend.
        if decode_impl is not None:
            out, k_c, v_c, pos_c = decode_impl(
                q[:, 0], cache["k"], cache["v"], cache["pos"], q_pos,
                k[:, 0], v[:, 0], window=window, logit_cap=cfg.attn_logit_softcap,
            )
            out = out[:, None]
        else:
            sc = cache["k"].shape[1]
            slot = (q_pos % sc).astype(jnp.int32)
            k_c = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
            v_c = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
            pos_c = jax.lax.dynamic_update_slice(cache["pos"], q_pos[None].astype(jnp.int32), (slot,))
            out = decode_attention(
                q[:, 0], k_c, v_c, pos_c, q_pos,
                window=window, logit_cap=cfg.attn_logit_softcap,
            )[:, None]
        new_cache = {"k": k_c, "v": v_c, "pos": pos_c}
    else:
        kernel = _flash_kernel(s)
        if kernel is not None:
            # positions is arange(s) on every path that gets here
            # (CausalLM.forward, CausalLM.prefill), so the kernel's structural
            # causal mask is the positional mask blocked_causal_attention builds
            out = kernel(q, k, v, window=window, logit_cap=cfg.attn_logit_softcap)
        else:
            from repro.sharding.context import model_axis_size

            ms = model_axis_size()
            out = blocked_causal_attention(
                q, k, v,
                window=window, logit_cap=cfg.attn_logit_softcap,
                chunk=cfg.attn_chunk, positions=positions,
                shard_chunk=(ms > 1 and cfg.num_heads % ms != 0),
            )
        if return_cache:
            sc = min(window, s) if window is not None else s
            new_cache = {
                "k": k[:, s - sc :].astype(cfg.param_dtype),
                "v": v[:, s - sc :].astype(cfg.param_dtype),
                "pos": positions[s - sc :].astype(jnp.int32),
            }
    out = out.reshape(b, s, hq * hd)
    return dense(out, p["wo"]), new_cache


def _apply_layer(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    idx_in_period: int,
    *,
    long_context: bool,
    positions: jax.Array,
    cache: Optional[dict],
    q_pos: Optional[jax.Array],
    return_cache: bool,
    decode_impl=None,
):
    """One layer (mixer + optional FFN). Returns (x, new_cache, aux_loss)."""
    kind = cfg.layer_kind(idx_in_period)
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln_mix"], cfg.norm_eps)
    if kind == "attn":
        window = cfg.window_for_layer(idx_in_period, long_context)
        with jax.named_scope(spans.ATTENTION):
            mix, new_cache = _attention(
                p["attn"], h, cfg,
                window=window, positions=positions, cache=cache,
                q_pos=q_pos, return_cache=return_cache, decode_impl=decode_impl,
            )
    else:
        if cache is not None and q_pos is not None:
            mix, new_cache = mamba_decode_step(p["mamba"], h, cfg, cache)
        else:
            mix, (h_final, tails) = mamba_forward(p["mamba"], h, cfg)
            new_cache = (
                {"ssm": h_final, "conv_x": tails["x"], "conv_b": tails["b"], "conv_c": tails["c"]}
                if return_cache
                else None
            )
    if cfg.use_post_norm:
        mix = rms_norm(mix, p["ln_mix_post"], cfg.norm_eps)
    x = x + mix

    if cfg.d_ff:
        h = rms_norm(x, p["ln_ffn"], cfg.norm_eps)
        with jax.named_scope(spans.MLP):
            if cfg.is_moe_layer(idx_in_period):
                out, aux = moe_mlp(
                    h, p["ffn"],
                    num_experts_per_tok=cfg.num_experts_per_tok,
                    capacity_factor=cfg.moe_capacity_factor,
                )
            else:
                out = gated_mlp(h, p["ffn"])
        if cfg.use_post_norm:
            out = rms_norm(out, p["ln_ffn_post"], cfg.norm_eps)
        x = x + out
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------

class CausalLM:
    """Functional causal LM; params are plain pytrees (scan-stacked blocks)."""

    def __init__(self, cfg: ArchConfig, long_context: bool = False, decode_impl=None):
        self.cfg = cfg
        self.long_context = long_context
        self.decode_impl = decode_impl

    # -- init ---------------------------------------------------------------
    def init(self, rng) -> PyTree:
        cfg = self.cfg
        k_embed, k_blocks, k_head = jax.random.split(rng, 3)
        dt = cfg.param_dtype
        v = cfg.padded_vocab

        if cfg.modality == "audio" and cfg.num_codebooks > 1:
            embed = (
                jax.random.normal(k_embed, (cfg.num_codebooks, v, cfg.d_model), jnp.float32) * 0.02
            ).astype(dt)
        else:
            embed = (jax.random.normal(k_embed, (v, cfg.d_model), jnp.float32) * 0.02).astype(dt)

        def block_params(key):
            ks = jax.random.split(key, cfg.scan_period)
            return {f"pos{i}": _init_layer_params(ks[i], cfg, i) for i in range(cfg.scan_period)}

        block_keys = jax.random.split(k_blocks, cfg.num_scan_blocks)
        blocks = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *[block_params(k) for k in block_keys]
        )

        params = {"embed": embed, "blocks": blocks, "ln_final": jnp.ones((cfg.d_model,), dt)}
        if not cfg.tie_embeddings:
            if cfg.modality == "audio" and cfg.num_codebooks > 1:
                params["head"] = (
                    jax.random.normal(k_head, (cfg.num_codebooks, cfg.d_model, v), jnp.float32)
                    * cfg.d_model ** -0.5
                ).astype(dt)
            else:
                params["head"] = init_dense(k_head, cfg.d_model, v, dt)
        return params

    # -- embedding / head -----------------------------------------------------
    @jax.named_scope(spans.EMBED)
    def embed_tokens(self, params, tokens, frontend_embeds=None):
        cfg = self.cfg
        if cfg.modality == "audio" and cfg.num_codebooks > 1:
            # tokens: (B, K, S) -> sum of per-codebook embeddings.
            x = sum(
                params["embed"][k][tokens[:, k]].astype(cfg.act_dtype)
                for k in range(cfg.num_codebooks)
            )
        else:
            x = params["embed"][tokens]  # (B, S, d)
        x = x.astype(cfg.act_dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if frontend_embeds is not None:
            f = frontend_embeds.shape[1]
            x = jnp.concatenate([frontend_embeds.astype(x.dtype), x[:, f:]], axis=1)
        return x

    @jax.named_scope(spans.LM_HEAD)
    def final_norm(self, params, x):
        return rms_norm(x, params["ln_final"], self.cfg.norm_eps)

    def _head(self, params, x):
        """The head matmul, in the activations' dtype, before the softcap."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            w = params["embed"]
            return jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))  # upcast fp8 -> act
        if cfg.modality == "audio" and cfg.num_codebooks > 1:
            return jnp.einsum("bsd,kdv->bskv", x, params["head"].astype(x.dtype))
        return dense(x, params["head"])

    @jax.named_scope(spans.LM_HEAD)
    def logits(self, params, x):
        return softcap(self._head(params, x).astype(jnp.float32), self.cfg.final_logit_softcap)

    # -- stacks ---------------------------------------------------------------
    def _run_stack(self, params, x, positions, *, return_cache=False):
        cfg = self.cfg
        aux0 = jnp.zeros((), jnp.float32)

        def block_fn(carry, block_p):
            x, aux = carry
            caches = []
            for i in range(cfg.scan_period):
                x, c, a = _apply_layer(
                    block_p[f"pos{i}"], x, cfg, i,
                    long_context=self.long_context, positions=positions,
                    cache=None, q_pos=None, return_cache=return_cache,
                )
                aux = aux + a
                caches.append(c)
            out = {f"pos{i}": caches[i] for i in range(cfg.scan_period)} if return_cache else None
            return (x, aux), out

        if cfg.remat:
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat_policy == "dots"
                else None
            )
            fn = jax.checkpoint(block_fn, policy=policy)
        else:
            fn = block_fn
        (x, aux), caches = jax.lax.scan(fn, (x, aux0), params["blocks"])
        return x, aux, caches

    # -- public API -------------------------------------------------------------
    def _hidden(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """The final-normed hidden states (B, S, d) and the router aux loss."""
        tokens = batch["tokens"]
        s = tokens.shape[-1]
        x = self.embed_tokens(params, tokens, batch.get("frontend_embeds"))
        positions = jnp.arange(s, dtype=jnp.int32)
        x, aux, _ = self._run_stack(params, x, positions, return_cache=False)
        return self.final_norm(params, x), aux

    def forward(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """batch: {tokens (B,S) or (B,K,S), frontend_embeds?} -> (logits, aux)."""
        x, aux = self._hidden(params, batch)
        return self.logits(params, x), aux

    def loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        x, aux = self._hidden(params, batch)
        labels = batch["labels"]
        with jax.named_scope(spans.LM_HEAD):
            out = self._head(params, x)
            if cfg.modality == "audio" and cfg.num_codebooks > 1:
                # out (B,S,K,V); labels (B,K,S)
                out = out.transpose(0, 2, 1, 3)
            nll = softmax_cross_entropy(
                out, labels, vocab=cfg.vocab_size, softcap=cfg.final_logit_softcap
            )
        mask = batch.get("loss_mask")
        if mask is None and cfg.frontend_tokens:
            m = jnp.ones(nll.shape, jnp.float32)
            mask = m.at[..., : cfg.frontend_tokens].set(0.0)
        if mask is not None:
            nll = nll * mask
            return nll.sum() / jnp.maximum(mask.sum(), 1.0) + cfg.router_aux_coef * aux
        return nll.mean() + cfg.router_aux_coef * aux

    # -- caches -------------------------------------------------------------------
    def init_cache(self, batch_size: int, cache_len: int) -> PyTree:
        """Empty per-scan-block caches, stacked on axis 0 (scan xs)."""
        cfg = self.cfg

        def one_layer(i):
            if cfg.layer_kind(i) == "mamba":
                return init_mamba_cache(cfg, batch_size, cfg.param_dtype)
            window = cfg.window_for_layer(i, self.long_context)
            sc = min(window, cache_len) if window is not None else cache_len
            return {
                "k": jnp.zeros((batch_size, sc, cfg.num_kv_heads, cfg.head_dim), cfg.param_dtype),
                "v": jnp.zeros((batch_size, sc, cfg.num_kv_heads, cfg.head_dim), cfg.param_dtype),
                "pos": jnp.full((sc,), -1, jnp.int32),
            }

        block = {f"pos{i}": one_layer(i) for i in range(cfg.scan_period)}
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.num_scan_blocks,) + x.shape).copy(), block
        )

    def prefill(self, params, batch) -> tuple[jax.Array, PyTree]:
        """Full-sequence prefill: returns (last-position logits, cache)."""
        tokens = batch["tokens"]
        s = tokens.shape[-1]
        x = self.embed_tokens(params, tokens, batch.get("frontend_embeds"))
        positions = jnp.arange(s, dtype=jnp.int32)
        x, _, caches = self._run_stack(params, x, positions, return_cache=True)
        return self.logits(params, self.final_norm(params, x)[:, -1:, :]), caches

    def decode_hidden(self, params, token, cache, pos):
        """``decode_step`` up to (and including) the final norm.

        Returns (x (B,1,d), new_cache).  Split out so callers that need the
        pre-logits hidden state — e.g. the continuous-batching engine, which
        computes logits outside a per-slot vmap to keep per-slot gathered
        cluster weights bitwise-identical to the shared path — can reuse the
        exact decode body."""
        cfg = self.cfg
        tok = token[..., None] if token.ndim == 1 else token[..., None]  # add S=1
        if cfg.modality == "audio" and cfg.num_codebooks > 1:
            tok = token[..., None]  # (B,K,1)
        x = self.embed_tokens(params, tok)
        positions = pos[None].astype(jnp.int32) if jnp.ndim(pos) == 0 else pos
        q_pos = positions[0]

        def block_fn(carry, scanned):
            x = carry
            block_p, block_cache = scanned
            new_caches = {}
            for i in range(cfg.scan_period):
                x, c, _ = _apply_layer(
                    block_p[f"pos{i}"], x, cfg, i,
                    long_context=self.long_context, positions=positions,
                    cache=block_cache[f"pos{i}"], q_pos=q_pos, return_cache=False,
                    decode_impl=self.decode_impl,
                )
                new_caches[f"pos{i}"] = c
            return x, new_caches

        x, new_cache = jax.lax.scan(block_fn, x, (params["blocks"], cache))
        return self.final_norm(params, x), new_cache

    def decode_step(self, params, token, cache, pos):
        """token: (B,) or (B,K); pos: scalar int32 (current position).

        Returns (logits (B,1,V...) , new_cache)."""
        x, new_cache = self.decode_hidden(params, token, cache, pos)
        return self.logits(params, x), new_cache
