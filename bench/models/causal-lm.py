"""A llama-style decoder (GQA, SwiGLU, untied head): the program's
``repro.models.CausalLM`` built from a configuration file's sizes, and the
model FLOPs of one token or one training example, counted from those sizes.

FLOPs are what the passes require: a multiply-add counts 2, training is 3x
the forward, and the forward that activation checkpointing recomputes is
not counted.  ``cfg`` holds ``d_model``, ``d_ff``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``num_layers`` and ``vocab_size``.
"""
from __future__ import annotations

FIELDS = ("num_layers", "d_model", "d_ff", "vocab_size", "num_heads", "num_kv_heads",
          "head_dim", "rope_theta", "norm_eps", "tie_embeddings", "dtype", "remat")


def build(cfg: dict):
    from repro.models import CausalLM
    from repro.models.config import ArchConfig

    arch = ArchConfig(name=cfg["name"], family="dense", **{k: cfg[k] for k in FIELDS})
    return CausalLM(arch)


def forward_flops_per_token(cfg: dict, keys: float) -> float:
    """One token's forward FLOPs when it attends ``keys`` keys."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hq, hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    proj = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = 3 * d * f
    attn = 2 * hq * hd * keys  # q.k and p.v, each hq*hd multiply-adds per key
    return cfg["num_layers"] * 2 * (proj + mlp + attn) + 2 * d * cfg["vocab_size"]


def causal_keys(seq_len: float) -> float:
    """Mean keys a position of a causal sequence attends: (S + 1) / 2."""
    return (seq_len + 1) / 2


def train_flops_per_example(cfg: dict, params: dict) -> float:
    """Forward and backward FLOPs of one training sequence of the traffic
    file's ``seq_len`` tokens."""
    seq = params["seq_len"]
    return 3.0 * seq * forward_flops_per_token(cfg, causal_keys(seq))


def serve_flops(cfg: dict, requests: list) -> dict:
    """FLOPs of one prefilled and one decoded token, averaged over served
    requests (``prompt`` and ``budget`` each): a prompt token attends the
    causal mean of its prompt, a decoded one the mean context it decodes at."""
    if not requests:
        return {"prefill_flops_per_token": 0.0, "decode_flops_per_token": 0.0}
    prompt = sum(len(r["prompt"]) for r in requests) / len(requests)
    budget = sum(r["budget"] for r in requests) / len(requests)
    return {
        "prefill_flops_per_token": forward_flops_per_token(cfg, causal_keys(prompt)),
        "decode_flops_per_token": forward_flops_per_token(cfg, prompt + budget / 2),
    }
