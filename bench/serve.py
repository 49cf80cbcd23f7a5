"""Serving cells: the window drives ``ContinuousFederatedServer.submit`` and
``step`` under open-loop traffic.

Set-up makes the ``(D, ...)`` cluster stack from the seed in one jitted
call, sizes the slot pool from the decode chunk's ``memory_analysis()``
beside that one stack, and warms each length bucket's prefill and admit
and the decode chunk.  The window offers requests at their due times and
steps the server; a request's first token exists once the ``step`` that
took it off the queue returns (admission samples it, the chunk after it
syncs).  Requests keep arriving after the window until those due in it
finish, for at most another window; any still unfinished then has failed.
"""
from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from .common import Cell, CompileCounter, info
from .train import weights_key

TRACE_SECONDS = 5.0
SAMPLE_LONGEST, SAMPLE_OTHERS = 4, 8


def stack_init(cell: Cell, seed: int):
    """One jitted call: each cluster's model from its own key, stacked."""
    import jax

    d = cell.traffic["clusters"]
    key = weights_key(seed)
    return jax.jit(lambda k: jax.vmap(lambda kk: cell.ref.init(kk, cell.config))(
        jax.random.split(k, d))), key


def fit_max_batch(model, stack, free_bytes: int, srv: dict) -> int:
    """Largest slot pool whose decode chunk fits beside the stack, read from
    the compiled chunk program's ``memory_analysis()``."""
    import jax
    from repro.serving.slots import build_slot_programs, init_slot_state

    _, _, chunk = build_slot_programs(model, temperature=0.0, gen_cap=srv["gen_cap"],
                                      chunk_steps=srv["chunk_steps"], stacked=True)
    for mb in srv["max_batch_candidates"]:
        state = jax.eval_shape(lambda mb=mb: init_slot_state(
            model, max_batch=mb, cache_len=srv["buckets"][-1] + srv["gen_cap"],
            gen_cap=srv["gen_cap"], federated=True, seed=0))
        ma = chunk.lower(stack, state).compile().memory_analysis()
        need = ma.temp_size_in_bytes + sum(x.size * x.dtype.itemsize
                                           for x in jax.tree.leaves(state))
        if need <= free_bytes - srv["headroom_bytes"]:
            info(f"max_batch {mb}: chunk temp {ma.temp_size_in_bytes} B, free {free_bytes} B")
            return mb
    raise RuntimeError(f"no max_batch in {srv['max_batch_candidates']} fits {free_bytes} B")


def build(cell: Cell, seed: int):
    import jax
    from repro.serving import ContinuousFederatedServer

    srv = cell.traffic["server"]
    model = cell.program_model.build(cell.config)
    init, key = stack_init(cell, seed)
    stack = init(key)
    jax.block_until_ready(stack)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    free = stats.get("bytes_limit", 16 * 2**30) - stats.get("bytes_in_use", 0)
    mb = srv.get("max_batch") or fit_max_batch(model, jax.eval_shape(lambda: stack), free, srv)
    server = ContinuousFederatedServer(
        model, stack, max_batch=mb, length_buckets=tuple(srv["buckets"]),
        gen_cap=srv["gen_cap"], chunk_steps=srv["chunk_steps"], temperature=0.0,
        seed=seed % 2**31)
    return server, init, key, mb


def warm(server, cell: Cell, seed: int) -> None:
    """Compile each bucket's prefill and admit and the decode chunk."""
    from repro.serving.engine import Request

    srv = cell.traffic["server"]
    rng = np.random.default_rng((seed, 1))
    for i, b in enumerate(srv["buckets"]):
        server.submit(Request(uid=-1 - i, prompt=rng.integers(
            0, cell.config["vocab_size"], size=b, dtype=np.int32),
            max_new_tokens=srv["chunk_steps"] + 1, cluster_id=i % cell.traffic["clusters"]))
    server.run()


def drive(server, reqs: list, seconds: float, spans: bool = False) -> dict:
    """Offer ``reqs`` at their due times and step the server until those due
    in the window have finished, or another window has passed."""
    import contextlib

    import jax
    from repro.serving.engine import Request

    span = jax.profiler.TraceAnnotation if spans else (lambda _n: contextlib.nullcontext())
    recs = {r["uid"]: dict(r) for r in reqs}
    due_in = [r["uid"] for r in reqs if r["due_s"] < seconds]
    waiting = deque()  # submitted, not yet taken off the server's queue
    late, i, n = [], 0, len(reqs)
    outstanding = set(due_in)
    backlog = None  # due in the window and unfinished when it closes
    t0 = time.perf_counter()
    with span("bench.window"):
        while outstanding:
            now = time.perf_counter() - t0
            if backlog is None and now >= seconds:
                backlog = len(outstanding)
            if now > 2 * seconds:
                break
            while i < n and reqs[i]["due_s"] <= now:
                r = reqs[i]
                server.submit(Request(uid=r["uid"], prompt=r["prompt"],
                                      max_new_tokens=r["budget"], cluster_id=r["cluster"]))
                waiting.append(r["uid"])
                late.append(now - r["due_s"])
                i += 1
            if server.pending() or server._occupied:
                before = server.pending()
                with span("bench.step"):
                    finished = server.step()
                t = time.perf_counter() - t0
                for _ in range(before - server.pending()):
                    recs[waiting.popleft()]["first_s"] = t
                for req in finished:
                    rec = recs[req.uid]
                    rec["done_s"], rec["output"] = t, req.output
                    outstanding.discard(req.uid)
            elif i < n:
                with span("bench.idle"):
                    time.sleep(max(0.0, reqs[i]["due_s"] - (time.perf_counter() - t0)))
    return {"recs": recs, "due_in": due_in, "lateness": late,
            "end_s": time.perf_counter() - t0, "backlog": backlog or 0}


def nearest_rank(xs, q: float) -> float:
    xs = sorted(xs)
    return float(xs[max(0, int(np.ceil(q * len(xs))) - 1)])


def summarize(res: dict, seconds: float) -> dict:
    recs, due = res["recs"], res["due_in"]
    end = res["end_s"]
    finished = [recs[u] for u in due if "done_s" in recs[u]]
    ttft = [recs[u].get("first_s", end) - recs[u]["due_s"] for u in due]
    tpot = [1e3 * (r["done_s"] - r["first_s"]) / (len(r["output"]) - 1)
            for r in finished if len(r["output"]) >= 2]
    tokens = sum(len(r["output"]) for r in recs.values()
                 if "done_s" in r and r["done_s"] <= seconds)
    late = res["lateness"]
    info(f"requests due {len(due)}, finished {len(finished)}, unfinished "
         f"{len(due) - len(finished)}; generator lateness median "
         f"{np.median(late) if late else 0:.4f} s, max {max(late) if late else 0:.4f} s; "
         f"drained at {end:.3f} s")
    return {
        "serve_ttft_p95_s": nearest_rank(ttft, 0.95),
        "serve_tpot_p95_ms": nearest_rank(tpot, 0.95) if tpot else float("nan"),
        "serve_tokens_per_s": tokens / seconds,
        "attempted": len(due),
        "failed": len(due) - len(finished),
    }


def sample(res: dict, seed: int) -> list:
    """The finished requests the reference checks: the longest outputs and
    others drawn from the seed."""
    done = [r for r in res["recs"].values() if "output" in r]
    done.sort(key=lambda r: (-len(r["output"]), r["uid"]))
    longest, rest = done[:SAMPLE_LONGEST], done[SAMPLE_LONGEST:]
    rng = np.random.default_rng((seed, 2))
    pick = rng.choice(len(rest), size=min(SAMPLE_OTHERS, len(rest)), replace=False)
    return longest + [rest[j] for j in sorted(pick)]


def served_rows(cell: Cell, reqs: list):
    """Each sampled request as the server runs it, padded to one length:
    the prompt left-padded to its bucket with its first token (the server's
    stated padding), then the served tokens.  Returns (tokens (N, L),
    positions whose logits chose each served token, served tokens)."""
    srv = cell.traffic["server"]
    length = srv["buckets"][-1] + srv["gen_cap"]
    rows, where = [], []
    for r in reqs:
        p = r["prompt"]
        blen = next(b for b in srv["buckets"] if len(p) <= b)
        seq = np.concatenate([np.full(blen - len(p), p[0], np.int32), p,
                              np.asarray(r["output"], np.int32)])
        where.append(np.arange(len(r["output"])) + blen - 1)
        rows.append(np.pad(seq, (0, length - len(seq))))
    return np.stack(rows), where


def served_gaps(cell: Cell, params_of, reqs: list, low: bool = False) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position.  With ``low`` the token is the one
    the lower-precision reference puts first there (the control)."""
    import jax
    import jax.numpy as jnp

    rows, where = served_rows(cell, reqs)
    ref = jax.jit(lambda p, t: cell.ref.logits(p, t[None], cell.config)[0])
    lowf = jax.jit(lambda p, t: cell.ref.logits(p, t[None], cell.config, low=True)[0])
    gaps = []
    for r, row, pos in zip(reqs, rows, where):
        p = params_of(r["cluster"])
        lg = np.asarray(ref(p, jnp.asarray(row)), np.float64)[pos]
        tok = (np.asarray(lowf(p, jnp.asarray(row)))[pos].argmax(-1) if low
               else np.asarray(r["output"]))
        gaps.append(lg.max(-1) - lg[np.arange(len(pos)), tok])
    return np.concatenate(gaps)


def readings(cell: Cell, seed: int, control: bool = True, seconds: float = 5.0) -> dict:
    """A serving cell's program and control on one seed: a window of
    ``seconds`` at the cell's own load, then the reference's gaps on the
    sampled requests' served tokens, and on the tokens the control would
    put first at the same positions."""
    import jax

    reqs = cell.inputs(seed, seconds)
    server, init, key, _ = build(cell, seed)
    warm(server, cell, seed)
    picked = sample(drive(server, reqs, seconds), seed)
    del server
    gc.collect()
    stack = init(key)
    params_of = lambda d: jax.tree.map(lambda x: x[d], stack)  # noqa: E731
    out = {"seed": seed, "program": {"served_logit_gap": float(
        served_gaps(cell, params_of, picked).max())}}
    if control:
        out["control"] = {"served_logit_gap": float(
            served_gaps(cell, params_of, picked, low=True).max())}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, setup_t0: float,
        fault: str | None = None) -> dict:
    import jax

    reqs = cell.inputs(seed, seconds)
    server, init, key, mb = build(cell, seed)
    if fault is not None:
        plant(server, fault)
    warm(server, cell, seed)
    counts0 = server.compile_counts()
    compiles = CompileCounter()
    setup_s = time.time() - setup_t0
    out = {"setup_s": setup_s, "max_batch": mb}
    with compiles:
        if trace:
            from .common import traced

            window = min(seconds, TRACE_SECONDS)
            steps0 = int(server._state["active_steps"])
            res, red = traced(lambda: drive(server, [r for r in reqs if r["due_s"] < window],
                                            window, spans=True))
            out["reduction"] = red
            out["decode_tokens"] = int(server._state["active_steps"]) - steps0
            out["prefill_tokens"] = sum(len(r["prompt"]) for r in res["recs"].values()
                                        if "first_s" in r)
            seconds = window
        else:
            res = drive(server, reqs, seconds)
    counts1 = server.compile_counts()
    extra = sum(counts1.values()) - sum(counts0.values())
    out.update(summarize(res, seconds), compiles_in_window=compiles.count + extra,
               window_s=seconds)
    info(f"compiles in window {out['compiles_in_window']} ({counts1})")
    out["memory_peak_bytes"] = cell.memory_peak()
    out["costs"] = cell.program_model.serve_flops(
        cell.config, [r for r in res["recs"].values() if "first_s" in r])

    picked = sample(res, seed)
    del server
    gc.collect()
    t0 = time.perf_counter()
    stack = init(key)
    params_of = lambda d: jax.tree.map(lambda x: x[d], stack)  # noqa: E731
    gaps = served_gaps(cell, params_of, picked)
    info(f"reference: {time.perf_counter() - t0:.1f} s over {len(picked)} requests, "
         f"{len(gaps)} served tokens")
    out["numbers"] = {"served_logit_gap": float(gaps.max()) if len(gaps) else float("inf")}
    return out


def plant(server, fault: str) -> None:
    """Break the timed path underneath the harness, for the harness's own
    test: ``altered_token`` changes every token the decode chunk emits."""
    if fault != "altered_token":
        raise ValueError(f"unknown fault {fault!r}")
    chunk = server._chunk_p
    vocab = server.model.cfg.vocab_size

    def altered(weights, state):
        state = chunk(weights, state)
        return {**state, "out": (state["out"] + 1) % vocab}

    altered._cache_size = chunk._cache_size
    server._chunk_p = altered
