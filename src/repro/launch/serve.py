"""Batched decode serving driver.

Prefills a batch of prompts and decodes tokens autoregressively with the
ring-buffer KV/SSM caches.  On CPU this drives reduced configs (see
examples/serve_decode.py); on TPU, build_serve() adds the sequence-sharded
cache + LSE-merge decode attention.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \
        --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import CausalLM


def grow_caches(model: CausalLM, cache, new_len: int):
    """Extend full-attention KV caches to ``new_len`` slots (pos = -1 padding).

    Sliding-window layers keep their ring buffers (size = window) — the ring
    overwrite is exactly the sliding-window eviction policy."""
    cfg = model.cfg

    def grow_layer(i, layer):
        if cfg.layer_kind(i) == "mamba" or "k" not in layer:
            return layer
        if cfg.window_for_layer(i, model.long_context) is not None:
            return layer
        sc = layer["k"].shape[2]
        pad = new_len - sc
        if pad <= 0:
            return layer
        def padk(x):  # (nblocks, B, Sc, H, hd)
            return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        return {
            "k": padk(layer["k"]),
            "v": padk(layer["v"]),
            "pos": jnp.pad(layer["pos"], ((0, 0), (0, pad)), constant_values=-1),
        }

    return {f"pos{i}": grow_layer(i, cache[f"pos{i}"]) for i in range(cfg.scan_period)}


def generate(model: CausalLM, params, prompts: jax.Array, gen_len: int,
             cache_len: int | None = None, temperature: float = 0.0, seed: int = 0):
    """prompts: (B, S) (or (B, K, S) audio). Returns generated tokens (B, gen)."""
    cfg = model.cfg
    s = prompts.shape[-1]
    cache_len = cache_len or (s + gen_len)
    logits, cache = jax.jit(model.prefill)(params, {"tokens": prompts})
    cache = grow_caches(model, cache, cache_len)
    step = jax.jit(model.decode_step)
    key = jax.random.PRNGKey(seed)
    outs = []

    def sample(logits, key):
        flat = logits[..., : cfg.vocab_size]
        if temperature <= 0:
            return jnp.argmax(flat, axis=-1)
        return jax.random.categorical(key, flat / temperature, axis=-1)

    # prefill produced a cache of length >= s; continue decoding from pos s.
    # rebuild a decode cache of cache_len and copy: for simplicity we decode
    # with the prefill cache when it is already long enough.
    tok = sample(logits[:, -1], key)
    if cfg.modality == "audio" and cfg.num_codebooks > 1:
        tok = tok.reshape(tok.shape[0], cfg.num_codebooks)
    for i in range(gen_len):
        outs.append(tok)
        key, sub = jax.random.split(key)
        logits, cache = step(params, tok, cache, jnp.int32(s + i))
        tok = sample(logits[:, -1] if logits.ndim == 3 else logits[:, -1], sub)
        if cfg.modality == "audio" and cfg.num_codebooks > 1:
            tok = tok.reshape(tok.shape[0], cfg.num_codebooks)
    return jnp.stack(outs, axis=1)


def serve_scenario(name: str, *, train_steps: int = 4, requests: int = 16,
                   max_batch: int = 8, gen: int = 16, seed: int = 0,
                   arch_overrides=None, length_buckets=(16, 32, 64),
                   continuous: bool = False, mesh=None):
    """Close the training->serving loop for one named federated scenario.

    Builds the scenario (it must use the ``lm-clustered`` corpus so the
    trace knows each cluster's successor table), trains it for
    ``train_steps`` scheduler steps, pulls the per-cluster models off the
    live runtime via ``cluster_params()`` into a federated server, and
    replays a Zipf per-cluster request trace against them.  Returns
    ``(server, done, history)``.

    ``continuous=True`` serves through the slot-pool
    :class:`~repro.serving.ContinuousFederatedServer` (mid-decode admission,
    device-side decode loop) with heavy-tailed per-request budgets on
    ``[1, gen]``; ``mesh`` (None / ``"auto"`` / a Mesh) then shards the
    stacked replica axis across the cluster mesh.
    """
    from repro.scenarios import build_scenario
    from repro.serving import (
        ContinuousFederatedServer, FederatedServer, synthetic_trace,
    )

    overrides = {"seed": seed}
    if arch_overrides:
        overrides["arch_overrides"] = arch_overrides
    run = build_scenario(name, **overrides)
    history = run.run(train_steps)
    if continuous:
        server = ContinuousFederatedServer(
            run.runtime.model, runtime=run.runtime, mesh=mesh,
            max_batch=max_batch, length_buckets=tuple(length_buckets),
            gen_cap=gen,
        )
        budgets = (1, gen)
    else:
        server = FederatedServer(
            run.runtime.model, runtime=run.runtime,
            max_batch=max_batch, length_buckets=tuple(length_buckets),
        )
        budgets = gen
    trace = synthetic_trace(
        run.dataset, num_requests=requests, prompt_lens=(8, 16),
        max_new_tokens=budgets, seed=seed,
    )
    for req in trace:
        server.submit(req)
    done = server.run()
    return server, done, history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scenario", default=None,
                    help="train this federated scenario briefly, then serve "
                         "its per-cluster models (e.g. federated-lm-serving)")
    ap.add_argument("--train-steps", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous slot-pool engine "
                         "(mid-decode admission, device-side decode loop)")
    ap.add_argument("--mesh", default=None,
                    help="'auto' to shard the cluster-replica stack across "
                         "a cluster mesh when enough devices exist")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.scenario is not None:
        server, done, _ = serve_scenario(
            args.scenario, train_steps=args.train_steps,
            requests=args.requests, max_batch=args.max_batch, gen=args.gen,
            continuous=args.continuous, mesh=args.mesh,
        )
        s = server.stats
        engine = "continuous" if args.continuous else "static"
        print(f"scenario={args.scenario} engine={engine} "
              f"clusters={server.num_clusters} requests={s.requests} "
              f"batches={s.batches}")
        print(f"{s.tokens_generated} tokens in {s.wall_s:.2f}s -> "
              f"{s.tokens_per_s:.1f} tok/s, {s.requests_per_s:.2f} req/s "
              f"(mean occupancy {s.mean_occupancy:.2f})")
        print(f"latency p50/p95 {s.latency_p50:.3f}/{s.latency_p95:.3f}s, "
              f"ttft p50/p95 {s.ttft_p50:.3f}/{s.ttft_p95:.3f}s")
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if cfg.modality == "audio" and cfg.num_codebooks > 1:
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, cfg.num_codebooks, args.prompt_len)),
            jnp.int32,
        )
    else:
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
        )
    t0 = time.time()
    out = generate(model, params, prompts, args.gen, temperature=args.temperature)
    dt = time.time() - t0
    toks = out.size
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} gen={args.gen}")
    print(f"generated {toks} tokens in {dt:.2f}s ({toks / dt:.1f} tok/s incl. compile)")
    print("sample tokens:", np.asarray(out)[0, :10].tolist())


if __name__ == "__main__":
    main()
