"""Bring-up smoke of the SD-FEEL main path on a TPU, at granite-8b widths.

Trains the federated round engine for three steps and serves per-cluster
replicas from the trained stack, through the same entry points the CLIs
compose (``make_run`` as in ``launch/train.py``, ``ContinuousFederatedServer``
as in ``launch/serve.py``).  Every width is granite-8b's published one; only
the depth is cut (``LAYERS``).  Weights are random, data is the repo's
synthetic clustered LM corpus, both made from ``--seed``.

    python chip_smoke.py                # one chip: pallas round step + serving
    python chip_smoke.py --four-chips   # four chips: clients and replicas
                                        # one per chip, vs dense / off-mesh

Each check prints a ``PASS``/``FAIL`` line; the first failure exits non-zero.
Times and memory are printed as information, not as a speed claim.  The
last line is one JSON object naming the device the run used.  Without a TPU
the script refuses to run and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ARCH = "granite-8b"
# depth cut and training sequence length that fit one v5e (16 GB) with the
# pallas round step; a second layer does not fit at seq 256 (PERF.md)
LAYERS = 1
SEQ = 512
GEN_CAP = 32
BUCKETS = (32, 64)
CHUNK_STEPS = 8
N_REQUESTS = 8
BATCH = 1  # sequences per client per local step
# Pallas and dense both accumulate the Lemma-1 sums in f32 and round once to
# the bf16 storage dtype, in different orders: they may differ by one bf16
# rounding step (2^-8 relative, 2^-7 at the bottom of a binade).
BF16_RTOL = 2.0 ** -7
F32_ATOL = 1e-6
# An f32 leaf on both sides: the kernel's MXU contractions at HIGHEST
# precision and the dense einsum differ only in summation order; a single
# bf16 MXU pass would miss by ~2^-9 relative and fail this.
F32_RTOL = 1e-5
# A fleet for the transition's MXU body: the dense anchor of the
# state-scaling lane (MnistCNN, 1000 clients in 8 clusters), largest leaf.
FLEET_CLIENTS, FLEET_CLUSTERS, FLEET_LEAF = 1000, 8, (320, 50)


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
    if not ok:
        sys.exit(1)


def info(msg: str) -> None:
    print(f"info {msg}", flush=True)


def require_tpu(count: int):
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform {platform!r} "
              f"({devices[0].device_kind}); no result", file=sys.stderr)
        sys.exit(2)
    if len(devices) < count:
        print(f"chip_smoke: needs {count} TPU chips, JAX found {len(devices)}; "
              "no result", file=sys.stderr)
        sys.exit(2)
    return devices


def peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB"


def batch_source(dataset, seed: int):
    """Pure in the protocol iteration ``k``: the prefetch pipeline runs ahead."""
    return lambda k: dataset.stacked_batch(BATCH, np.random.default_rng((seed, k)))


def train(cfg, *, clients: int, clusters: int, seq: int, seed: int,
          steps: int, mesh=None):
    """``make_run`` on a ring, then ``steps`` round-engine steps."""
    from repro.core.config import ExecSpec, ModelSpec, RunConfig
    from repro.core.runtime import make_run
    from repro.data import FederatedLM
    from repro.models import CausalLM

    t0 = time.time()
    dataset = FederatedLM.generate_clustered(
        clients, 16, seq, cfg.vocab_size, clusters, seed=seed,
    )
    runtime = make_run(RunConfig(
        model=ModelSpec(kind="causal-lm", instance=CausalLM(cfg)),
        exec=ExecSpec(scheduler="round", backend="auto", topology="ring",
                      tau1=2, tau2=1, alpha=1, learning_rate=0.01, mesh=mesh),
        num_clients=clients, num_clusters=clusters, seed=seed,
    ))
    jax.block_until_ready(runtime.scheduler.params)
    info(f"setup (data, init of {clients} clients) {time.time() - t0:.1f} s")
    source = batch_source(dataset, seed)
    losses = []
    for s in range(1, steps + 1):
        t0 = time.time()
        ev = runtime.step(source)
        step_losses = np.asarray(ev.losses, np.float64)  # syncs the step
        losses.extend(step_losses.tolist())
        info(f"train step {s} {time.time() - t0:.2f} s"
             f"{' (includes compile)' if s == 1 else ''} "
             f"losses {step_losses.tolist()}")
    return runtime, dataset, source, losses


def compiled_step(runtime, source):
    """The round step compiled for one step's operands (a compile-cache hit)."""
    from repro.core.pipeline import stack_window

    sched = runtime.scheduler
    window = stack_window(source, 1, sched.iterations_per_step)
    return sched._round_step.lower(sched.params, sched.opt_state, window).compile()


def mlp_leaf(params):
    return params["blocks"]["pos0"]["ffn"]["w_gate"]


def runtime_dense(runtime):
    from repro.core.backends import DenseBackend

    proto = runtime.scheduler._proto
    return DenseBackend(proto.clusters, proto.P(), runtime.scheduler.fl.alpha)


def agree_with_dense(name: str, got, leaf, dense, rtol: float = BF16_RTOL,
                     why: str = "bf16 storage, f32 accumulation") -> None:
    """``got`` vs the dense Lemma-1 einsum of ``leaf`` (fp32 reference)."""
    with jax.default_matmul_precision("highest"):
        ref = dense.transition({"w": leaf}, "inter")["w"]
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    err = np.abs(got - ref)
    bound = rtol * np.abs(ref) + F32_ATOL
    check(name, bool(np.all(err <= bound)),
          f"leaf {leaf.shape} {leaf.dtype}: max |diff| {err.max():.3e}, "
          f"{np.count_nonzero(err)} of {err.size} elements differ, tolerance "
          f"{rtol:.4g}*|dense| + {F32_ATOL:g} ({why})")


def fleet_transition(seed: int) -> None:
    """The fused transition's MXU body, taken when C*D is past what the VPU
    body unrolls, on one random f32 fleet leaf against the dense einsum."""
    from repro.core import ClusterSpec, mixing_matrix, ring
    from repro.core.backends import DenseBackend, PallasBackend
    from repro.kernels.fused_transition.kernel import VPU_MAX_TERMS

    c, d = FLEET_CLIENTS, FLEET_CLUSTERS
    clusters = ClusterSpec.uniform(c, d)
    p = mixing_matrix(ring(d), clusters.m_tilde())
    pallas = PallasBackend(clusters, p, 1)
    leaf = jax.random.normal(jax.random.PRNGKey(seed), (c,) + FLEET_LEAF)
    check("fleet takes the MXU body", c * d > VPU_MAX_TERMS and not pallas.interpret,
          f"C*D = {c * d} > {VPU_MAX_TERMS}, interpret={pallas.interpret}")
    agree_with_dense("pallas transition == dense (inter, fleet)",
                     pallas.transition({"w": leaf}, "inter")["w"], leaf,
                     DenseBackend(clusters, p, 1), rtol=F32_RTOL,
                     why="f32 storage and accumulation, summation order")


def fit_max_batch(model, stack, free_bytes: int, candidates=(8, 4, 2, 1)):
    """Largest slot pool whose decode chunk fits ``free_bytes``, read from
    the compiled chunk program's ``memory_analysis()``.  Each slot gathers
    its own cluster's replica (ROADMAP S1), so the chunk's temp grows by
    about one cluster model per slot."""
    from repro.serving.slots import build_slot_programs, init_slot_state

    _, _, chunk = build_slot_programs(model, temperature=0.0, gen_cap=GEN_CAP,
                                      chunk_steps=CHUNK_STEPS, stacked=True)
    for mb in candidates:
        state = jax.eval_shape(lambda mb=mb: init_slot_state(
            model, max_batch=mb, cache_len=BUCKETS[-1] + GEN_CAP,
            gen_cap=GEN_CAP, federated=True, seed=0))
        ma = chunk.lower(stack, state).compile().memory_analysis()
        state_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
        need = ma.temp_size_in_bytes + state_bytes
        info(f"decode chunk max_batch={mb}: temp {ma.temp_size_in_bytes / 1e9:.3f} GB, "
             f"slot state {state_bytes / 1e6:.1f} MB, free {free_bytes / 1e9:.3f} GB")
        if need <= free_bytes:
            return mb
    check("serving fits", False, f"no max_batch in {candidates} fits")


def serve(model, dataset, stack=None, *, runtime=None, mesh=None, max_batch: int,
          seed: int, publish: bool):
    from repro.serving import ContinuousFederatedServer, synthetic_trace

    server = ContinuousFederatedServer(
        model, stack, runtime=runtime, mesh=mesh, max_batch=max_batch,
        length_buckets=BUCKETS, gen_cap=GEN_CAP, chunk_steps=CHUNK_STEPS,
    )
    if publish:
        # hot-swap through the runtime: staged now, flipped at the first
        # admission boundary (the pool is empty)
        check("sync_from", server.sync_from() is True, "published the runtime's stack")
    trace = synthetic_trace(dataset, num_requests=N_REQUESTS, prompt_lens=(16, 64),
                            max_new_tokens=(1, GEN_CAP), seed=seed)
    for req in trace:
        server.submit(req)
    t0 = time.time()
    done = server.run()
    info(f"served {len(done)} requests, {server.stats.tokens_generated} tokens "
         f"in {time.time() - t0:.2f} s (includes compile)")
    return server, trace, done


def check_served(server, trace, done, vocab: int) -> None:
    from repro.serving.engine import _bucket_len

    check("all requests served", len(done) == len(trace) == N_REQUESTS,
          f"{len(done)}/{len(trace)}")
    bad = [r.uid for r in trace
           if not (1 <= len(r.output) <= r.max_new_tokens)
           or not np.all((r.output >= 0) & (r.output < vocab))]
    check("outputs in budget and vocabulary", not bad,
          f"lengths {[len(r.output) for r in trace]} vs budgets "
          f"{[r.max_new_tokens for r in trace]}; bad uids {bad}")
    used = {_bucket_len(r.prompt.shape[-1], server.buckets) for r in trace}
    counts = server.compile_counts()
    check("serving compiled once per shape",
          counts["decode"] == 1 and counts["prefill"] == counts["admit"] == len(used),
          f"{counts} over {len(used)} prompt buckets")
    check("no rejected publish", server.rejected == 0, f"rejected={server.rejected}")


def one_chip(args, devices) -> None:
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(ARCH), num_layers=LAYERS)
    info(f"{ARCH} widths: d_model {cfg.d_model}, heads {cfg.num_heads}/"
         f"{cfg.num_kv_heads}x{cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
         f"{cfg.vocab_size}, {cfg.dtype}, remat {cfg.remat}; layers {cfg.num_layers}")
    runtime, dataset, source, losses = train(
        cfg, clients=4, clusters=2, seq=SEQ, seed=args.seed, steps=3)
    sched = runtime.scheduler
    backend = sched.backend
    check("backend", backend.name == "pallas" and backend.interpret is False,
          f"auto resolved to {backend.name!r}, interpret={backend.interpret}")
    n = sched._round_step._cache_size()
    check("round step compiled once", n == 1, f"{n} compiled shape(s) over 3 steps")
    compiled = compiled_step(runtime, source)
    calls = compiled.as_text().count("tpu_custom_call")
    check("pallas kernels in the round step", calls > 0, f"{calls} tpu_custom_call ops")
    ma = compiled.memory_analysis()
    info(f"round step memory_analysis: arguments {ma.argument_size_in_bytes / 1e9:.3f} GB, "
         f"aliased {ma.alias_size_in_bytes / 1e9:.3f} GB, temp "
         f"{ma.temp_size_in_bytes / 1e9:.3f} GB")
    check("losses finite", bool(np.all(np.isfinite(losses))), f"{losses}")
    leaf = mlp_leaf(sched.params)
    agree_with_dense("pallas transition == dense (inter)",
                     backend.transition({"w": leaf}, "inter")["w"], leaf,
                     runtime_dense(runtime))
    fleet_transition(args.seed)
    info(f"peak device memory after training {peak_gb(devices[0])}")

    stack = jax.eval_shape(runtime.cluster_params)
    stack_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(stack))
    stats = devices[0].memory_stats()
    # the server holds one copy of the stack; a second is staged until the
    # hot swap flips at the first admission, before any decode chunk
    free = stats["bytes_limit"] - stats["bytes_in_use"] - 2 * stack_bytes
    max_batch = fit_max_batch(runtime.model, stack, free)
    info(f"max_batch {max_batch}: cluster stack {stack_bytes / 1e9:.3f} GB "
         f"({jax.tree.leaves(stack)[0].dtype})")
    server, trace, done = serve(runtime.model, dataset, runtime=runtime,
                                max_batch=max_batch, seed=args.seed, publish=True)
    check_served(server, trace, done, cfg.vocab_size)
    check("hot swap", server.swaps == 1, f"swaps={server.swaps}")
    info(f"device {devices[0].device_kind}; peak device memory {peak_gb(devices[0])}")


def four_chips(args, devices) -> None:
    """Clients one per chip (collective backend under shard_map) and serving
    replicas one per chip, each against its single-device counterpart."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(ARCH), num_layers=LAYERS)
    runtime, dataset, source, losses = train(
        cfg, clients=4, clusters=4, seq=SEQ, seed=args.seed, steps=3,
        mesh="auto")
    sched = runtime.scheduler
    check("backend", sched.backend.name == "collective" and sched.mesh is not None,
          f"auto resolved to {sched.backend.name!r} on mesh "
          f"{None if sched.mesh is None else dict(sched.mesh.shape)}")
    n = sched._round_step._cache_size()
    check("round step compiled once", n == 1, f"{n} compiled shape(s) over 3 steps")
    leaves = jax.tree.leaves(sched.params)
    one_per_chip = all(
        len(x.addressable_shards) == 4
        and all(s.data.shape[0] == 1 for s in x.addressable_shards)
        and len({s.device for s in x.addressable_shards}) == 4
        for x in leaves)
    check("one client per chip", one_per_chip,
          f"{len(leaves)} stacked leaves, each 4 shards of leading size 1")
    text = compiled_step(runtime, source).as_text()
    permutes = text.count("collective-permute")
    check("collectives in the round step", permutes > 0,
          f"{permutes} collective-permute ops")
    check("losses finite", bool(np.all(np.isfinite(losses))), f"{losses}")
    leaf = mlp_leaf(sched.params)
    got = sched.backend.transition({"w": leaf}, "inter")["w"]
    agree_with_dense("collective transition == dense (inter)", got,
                     jax.device_put(leaf, devices[0]), runtime_dense(runtime))

    # on the host, so neither server keeps a second device copy of it
    stack = jax.device_get(runtime.cluster_params())
    replicas, trace, done = serve(runtime.model, dataset, stack, mesh="auto",
                                  max_batch=4, seed=args.seed, publish=False)
    check("one replica per chip", replicas.mesh is not None and all(
        len(x.addressable_shards) == 4 for x in jax.tree.leaves(replicas.active_params)),
        f"cluster mesh {None if replicas.mesh is None else dict(replicas.mesh.shape)}")
    check_served(replicas, trace, done, cfg.vocab_size)
    del replicas  # the off-mesh server holds all four replicas on chip 0
    _, trace_off, _ = serve(runtime.model, dataset, stack, mesh=None,
                            max_batch=4, seed=args.seed, publish=False)
    first = next((a.uid for a, b in zip(trace, trace_off)
                  if not np.array_equal(a.output, b.output)), None)
    check("replicas == off-mesh server, token for token", first is None,
          "all requests agree" if first is None else
          f"first differing request uid {first}: mesh "
          f"{trace[first].output.tolist()} vs off-mesh {trace_off[first].output.tolist()}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path and what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    count = 4 if args.four_chips else 1
    devices = require_tpu(count)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import enable_compile_cache

    info(f"compile cache {enable_compile_cache()}")
    t0 = time.time()
    (four_chips if args.four_chips else one_chip)(args, devices)
    info(f"total {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
