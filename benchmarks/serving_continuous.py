"""Continuous-batching serving lane: slot-pool admission vs static drain.

The static engine admits a batch, decodes lock-step until the *slowest*
member finishes, and pays one host round-trip per decoded token.  Under the
Zipf cluster mix with heavy-tailed per-request budgets (most requests want
a few tokens, the tail wants many — ``traffic.heavy_tail_ints``), that is
the worst case: every batch is a straggler convoy.  This lane replays one
trace against three arms:

* ``static``      — :class:`~repro.serving.FederatedServer` drain baseline,
  pinned to the same fixed cache length as the slot pool so the comparison
  is mask-identical (and bitwise-comparable);
* ``continuous``  — :class:`~repro.serving.ContinuousFederatedServer`:
  finished requests free their slot mid-decode, admission is a jitted
  constant-shape scatter, and decode runs device-side in K-step
  ``lax.while_loop`` chunks (one ``done``-vector sync per chunk);
* ``continuous+mesh`` — the same engine with the stacked ``(D, ...)``
  replica axis sharded one cluster per device, in this process, and its
  outputs compared bitwise against the continuous arm.  It runs only when
  the host has at least ``D`` devices (on CPU, start the process with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``); otherwise it is
  reported as not run.

In-bench gates (all hard asserts, mirrored by the CI schema check):

* fp32/greedy outputs of the continuous arm are bitwise-identical to the
  static arm for every request on the trace;
* the decode chunk compiled exactly once and prefill/admit compiled exactly
  once per length bucket — no admission pattern recompiles;
* ``qps_continuous / qps_static > 1``.

Results land in ``results/BENCH_serving_continuous.json``.

Usage:
    PYTHONPATH=src python -m benchmarks.serving_continuous            # full
    PYTHONPATH=src python -m benchmarks.serving_continuous --smoke    # CI gate
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np

from repro.scenarios import build_scenario
from repro.serving import ContinuousFederatedServer, FederatedServer, ServeStats
from repro.serving.engine import _bucket_len
from repro.serving.traffic import synthetic_trace

from .common import RESULTS, ensure_results, timer

JSON_PATH = os.path.join(RESULTS, "BENCH_serving_continuous.json")
ROW_KEYS = ("arm", "requests", "tokens", "seconds", "qps", "tokens_per_sec",
            "decode_steps", "mean_occupancy", "ttft_p95", "latency_p95")
HEADLINE_KEYS = ("qps_static", "qps_continuous", "qps_mesh", "qps_ratio",
                 "bitwise_continuous_vs_static", "bitwise_mesh_vs_continuous",
                 "decode_compiles", "prefill_compiles", "compiled_buckets",
                 "occupancy_static", "occupancy_continuous")

SCENARIO = "lm-serving-continuous"
BUCKETS = (16, 32)
MAX_BATCH = 8
GEN_CAP = 32
CHUNK_STEPS = 8
# fp32 so the continuous==static and mesh==continuous checks are exact
TINY_ARCH = dict(num_layers=2, d_model=32, d_ff=64, num_heads=2,
                 num_kv_heads=1, head_dim=16, dtype="float32", remat=False)


def _fresh(trace):
    """Unserved copies (the engine mutates Request.output in place)."""
    return [dataclasses.replace(r, output=None, latency_s=0.0) for r in trace]


def _setup(train_steps: int, n_requests: int):
    """Deterministic scenario + trace."""
    run = build_scenario(SCENARIO, arch_overrides=TINY_ARCH)
    run.run(train_steps)
    trace = synthetic_trace(
        run.dataset, num_requests=n_requests, prompt_lens=(8, 24),
        max_new_tokens=(1, GEN_CAP), seed=0,
    )
    return run, trace


def _replay(server, trace, warmup):
    """Warm the compile caches, reset stats, then serve ``trace`` timed."""
    for r in _fresh(warmup):
        server.submit(r)
    server.run()
    server.stats = ServeStats()
    for r in trace:
        server.submit(r)
    done = server.run()
    s = server.stats
    return done, {
        "requests": s.requests, "tokens": s.tokens_generated,
        "seconds": s.wall_s, "qps": s.requests_per_s,
        "tokens_per_sec": s.tokens_per_s, "decode_steps": s.decode_steps,
        "mean_occupancy": s.mean_occupancy,
        "ttft_p95": s.ttft_p95, "latency_p95": s.latency_p95,
    }


def _mesh_arm(model, stack, trace, num_clusters: int):
    """Continuous serving with the replica stack sharded one cluster per
    device, in this process.  Returns ``(row, served, mesh)``, or ``None``
    when the host has fewer than ``num_clusters`` devices (on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count`` before starting)."""
    from repro.launch.mesh import make_cluster_mesh

    if len(jax.devices()) < num_clusters:
        return None
    mesh = make_cluster_mesh(num_clusters)
    server = ContinuousFederatedServer(
        model, stack, mesh=mesh,
        max_batch=MAX_BATCH, length_buckets=BUCKETS, gen_cap=GEN_CAP,
        chunk_steps=CHUNK_STEPS,
    )
    served = _fresh(trace)
    _, row = _replay(server, served, trace)
    return row, served, mesh


def main(smoke: bool = False) -> dict:
    ensure_results()
    elapsed = timer()
    train_steps = 24 if smoke else 48
    n_requests = 96 if smoke else 256

    run, trace = _setup(train_steps, n_requests)
    sc = run.scenario
    model = run.runtime.model
    stack = run.runtime.cluster_params()
    budgets = [r.max_new_tokens for r in trace]
    used_buckets = sorted({_bucket_len(r.prompt.shape[-1], BUCKETS) for r in trace})
    print(f"continuous serving: {sc.num_clusters} clusters, {n_requests} "
          f"requests, budgets [{min(budgets)}, {max(budgets)}] "
          f"(median {int(np.median(budgets))}), buckets {used_buckets}")

    rows = []
    static = FederatedServer(
        model, stack, max_batch=MAX_BATCH, length_buckets=BUCKETS,
        cache_len=BUCKETS[-1] + GEN_CAP,  # slot-pool cache length: masks match
    )
    static_done = _fresh(trace)
    _, row = _replay(static, static_done, trace)
    rows.append({"arm": "static", **row})

    cont = ContinuousFederatedServer(
        model, stack, max_batch=MAX_BATCH, length_buckets=BUCKETS,
        gen_cap=GEN_CAP, chunk_steps=CHUNK_STEPS,
    )
    cont_done = _fresh(trace)
    _, row = _replay(cont, cont_done, trace)
    rows.append({"arm": "continuous", **row})

    # gate 1: fp32/greedy continuous == static, request for request
    by_uid = {r.uid: r for r in static_done}
    bitwise = all(np.array_equal(r.output, by_uid[r.uid].output)
                  for r in cont_done)
    assert bitwise, "continuous decode diverged bitwise from the static drain"

    # gate 2: compiled shapes only — no admission pattern recompiled anything
    counts = cont.compile_counts()
    assert counts["decode"] == 1, (
        f"decode chunk recompiled: {counts['decode']} compiles (expected 1)"
    )
    assert counts["prefill"] == len(used_buckets) == counts["admit"], (
        f"per-bucket programs recompiled: {counts} vs {len(used_buckets)} buckets"
    )

    mesh_arm = _mesh_arm(model, stack, trace, sc.num_clusters)
    if mesh_arm is None:
        print(f"  continuous+mesh: not run ({len(jax.devices())} devices < "
              f"{sc.num_clusters} clusters)")
        mesh_bitwise, mesh_axes = None, None
    else:
        mesh_row, mesh_done, mesh = mesh_arm
        mesh_bitwise = all(
            np.array_equal(a.output, b.output) for a, b in zip(mesh_done, cont_done)
        )
        assert mesh_bitwise, "mesh-sharded replicas diverged from the vmap fallback"
        mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        rows.append({"arm": "continuous+mesh", **mesh_row})

    for r in rows:
        print(f"  {r['arm']:16s} {r['qps']:8.2f} req/s {r['tokens_per_sec']:9.1f} "
              f"tok/s  occ {r['mean_occupancy']:.2f}  "
              f"p95 latency {r['latency_p95']:.3f}s")

    qps_static = rows[0]["qps"]
    qps_cont = rows[1]["qps"]
    ratio = qps_cont / qps_static
    payload = {
        "config": {
            "scenario": SCENARIO,
            "num_clients": sc.num_clients, "num_clusters": sc.num_clusters,
            "vocab_size": sc.vocab_size, "seq_len": sc.seq_len,
            "train_steps": train_steps, "requests": n_requests,
            "max_batch": MAX_BATCH, "gen_cap": GEN_CAP,
            "chunk_steps": CHUNK_STEPS, "buckets": list(BUCKETS),
            "budget_law": "heavy-tail [1, gen_cap] exp 1.1",
            "mesh_devices": len(jax.devices()), "mesh_axes": mesh_axes,
            "smoke": smoke, "jax_backend": jax.default_backend(),
            "arch": "2L d_model=32 d_ff=64 fp32",
        },
        "rows": rows,
        "headline": {
            "qps_static": qps_static,
            "qps_continuous": qps_cont,
            "qps_mesh": rows[2]["qps"] if mesh_arm is not None else None,
            "qps_ratio": ratio,
            "bitwise_continuous_vs_static": bitwise,
            "bitwise_mesh_vs_continuous": mesh_bitwise,
            "decode_compiles": counts["decode"],
            "prefill_compiles": counts["prefill"],
            "compiled_buckets": len(used_buckets),
            "occupancy_static": rows[0]["mean_occupancy"],
            "occupancy_continuous": rows[1]["mean_occupancy"],
        },
        "bench_seconds": elapsed(),
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {JSON_PATH}")
    print(f"  continuous admission: {ratio:.2f}x static-drain qps "
          f"({qps_cont:.2f} vs {qps_static:.2f} req/s), occupancy "
          f"{rows[0]['mean_occupancy']:.2f} -> {rows[1]['mean_occupancy']:.2f}")
    assert ratio > 1.0, (
        f"continuous batching regressed: {ratio:.2f}x static qps on the "
        f"heavy-tailed trace (slot refill should beat the straggler convoy)"
    )
    return payload["headline"]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for the CI regression gate")
    main(smoke=ap.parse_args().smoke)
