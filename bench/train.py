"""Training cells: the window drives ``FederationRuntime.step``, one
SD-FEEL round (``tau1 * tau2`` local iterations and the transitions, for
the whole fleet) per call.

Set-up builds the runtime with the benchmark's weights and inputs, then
drives it through its first ``CHECKED_ROUNDS`` rounds with the window's
own call and batch source: they compile the round step and are the rounds
the reference follows.  The same runtime then runs the window.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from . import counters, reference
from .common import Cell, CompileCounter, info

CHECKED_ROUNDS = 3
FAULTS = ("half_batch", "no_transition")  # planted in the reference
TRACE_SECONDS = 3.0


def weights_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def build(cell: Cell, seed: int, fault: str | None = None):
    """The program's runtime, its batch source and the weights it starts from."""
    import jax
    from repro.core.config import ExecSpec, ModelSpec, RunConfig
    from repro.core.runtime import make_run

    cfg, fed, algo = cell.config, cell.traffic["federation"], cell.config["training"]
    source, _ = cell.inputs(seed)
    model = cell.program_model.build(cfg)
    init = jax.jit(lambda key: cell.ref.init(key, cfg))
    key = weights_key(seed)
    # the program's init draws from its own key: hand it the benchmark's weights
    model.init = lambda _key: init(key)
    runtime = make_run(RunConfig(
        model=ModelSpec(instance=model),
        exec=ExecSpec(scheduler="round", backend=algo["backend"], topology=algo["topology"],
                      tau1=algo["tau1"], tau2=algo["tau2"], alpha=algo["alpha"],
                      learning_rate=algo["learning_rate"], mesh=fed.get("mesh")),
        num_clients=fed["clients"], num_clusters=fed["clusters"], seed=seed % 2**31,
    ))
    if fault is not None:
        plant(runtime, fault)
    return runtime, source, init, key


def plant(runtime, fault: str) -> None:
    """Break the timed path underneath the harness, for the harness's own
    test: ``unchanged`` returns the state it was given, ``no_transition``
    leaves every average out, ``half_batch`` trains each client on half
    of its batch."""
    import jax

    sched = runtime.scheduler
    step = sched._round_step
    if fault == "unchanged":
        sched._round_step = lambda p, s, b, *a: (p, s, step(
            jax.tree.map(lambda x: x.copy(), p), s, b, *a)[2])
    elif fault == "no_transition":
        # the round step calls the backend while it is traced, at the first step
        sched.backend.transition = lambda stacked, event, **kw: stacked
    elif fault == "half_batch":
        def half(b):
            n = b.shape[2]
            return b[:, :, : n // 2] if n > 1 else b[:, :, :, : b.shape[3] // 2]

        sched._round_step = lambda p, s, b, *a: step(p, s, jax.tree.map(half, b), *a)
    else:
        raise ValueError(f"unknown fault {fault!r}")


def leaf_change_sq(params, w0):
    """Per-leaf squared norm over all clients of ``params - w0`` (float32)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.leaves(jax.tree.map(lambda a, b: jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)[None])), params, w0))


def checked_rounds(runtime, source, w0) -> dict:
    """Drive the first rounds through ``runtime.step``; record what the
    reference compares."""
    import jax

    sq = jax.jit(leaf_change_sq)

    def change(params, w0):
        return np.sqrt(np.asarray(sq(params, w0), np.float64))

    losses, out = [], {}
    for r in range(1, CHECKED_ROUNDS + 1):
        ev = runtime.step(source)
        losses.extend(np.asarray(ev.losses, np.float64).tolist())
        if r == 1:
            out["change1"] = change(runtime.scheduler.params, w0)
    out["change_last"] = change(runtime.scheduler.params, w0)
    out["losses"] = losses
    return out


def run_window(runtime, source, seconds: float, spans: bool = False) -> tuple[int, float]:
    """Step until ``seconds`` have passed; each step waits for the one
    before it, so at most one round is queued behind the running one.
    Returns (rounds, seconds from the first dispatch to the last result)."""
    import contextlib

    import jax

    span = jax.profiler.TraceAnnotation if spans else (lambda _n: contextlib.nullcontext())
    n, prev = 0, None
    t0 = time.perf_counter()
    with span("bench.window"):
        while True:
            with span("bench.dispatch"):
                ev = runtime.step(source)
            n += 1
            if prev is not None:
                with span("bench.wait"):
                    prev.block_until_ready()
            prev = ev.losses
            if time.perf_counter() - t0 >= seconds:
                break
        with span("bench.wait"):
            prev.block_until_ready()
    return n, time.perf_counter() - t0


def costs(cell: Cell, runtime) -> dict:
    """Model FLOPs and kernel FLOPs and bytes of one round, from shapes."""
    import jax

    fed, algo, params = cell.traffic["federation"], cell.config["training"], cell.traffic["params"]
    leaves = jax.tree.leaves(runtime.scheduler.params)
    shapes = [x.shape for x in leaves]
    itemsize = leaves[0].dtype.itemsize
    iters = algo["tau1"] * algo["tau2"]
    c, d = fed["clients"], fed["clusters"]
    examples = c * iters * params["batch"]
    model_flops = examples * cell.program_model.train_flops_per_example(cell.config, params)
    intra = counters.transition_cost(shapes, itemsize, c, d, 0)
    inter = counters.transition_cost(shapes, itemsize, c, d, algo["alpha"])
    sgd = counters.sgd_cost(shapes, itemsize, itemsize)
    return {
        "model_flops": model_flops,
        "transition": (algo["tau2"] * intra[0] + inter[0], algo["tau2"] * intra[1] + inter[1]),
        "sgd": (iters * sgd[0], iters * sgd[1]),
    }


def readings(cell: Cell, seed: int, control: bool = True, seconds: float | None = None,
             program: bool = True) -> dict:
    """The program's checked rounds on one seed and, with ``control``, the
    control and the faults planted in the reference, each compared with the
    reference as a run compares the program.  A state left unchanged reads
    1 on both change gaps by construction and needs no run.  ``seconds`` is
    not used: the checked rounds need no window."""
    import jax

    fed, algo = cell.traffic["federation"], cell.config["training"]
    out = {"seed": seed}
    if program:
        runtime, source, init, key = build(cell, seed)
        prog = checked_rounds(runtime, source, init(key))
        del runtime
        gc.collect()
    else:
        source, _ = cell.inputs(seed)
        init, key = jax.jit(lambda k: cell.ref.init(k, cell.config)), weights_key(seed)
    kw = dict(clients=fed["clients"], clusters=fed["clusters"], tau1=algo["tau1"],
              tau2=algo["tau2"], alpha=algo["alpha"], lr=algo["learning_rate"],
              topology=algo["topology"], rounds=CHECKED_ROUNDS)
    ref = reference.follow(cell.ref, cell.config, init(key), source, **kw)
    if program:
        out["program"] = reference.compare(prog, ref)["numbers"]
    if not control:
        return out
    out["control"] = reference.compare(
        reference.follow(cell.ref, cell.config, init(key), source, low=True, **kw), ref)["numbers"]
    for fault in FAULTS:
        out[fault] = reference.compare(
            reference.follow(cell.ref, cell.config, init(key), source, fault=fault, **kw),
            ref)["numbers"]
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, setup_t0: float,
        fault: str | None = None) -> dict:
    import jax

    runtime, source, init, key = build(cell, seed, fault)
    backend = runtime.scheduler.backend
    want = cell.traffic["federation"]["backend"]
    if backend.name != want or getattr(backend, "interpret", False):
        raise RuntimeError(f"round step resolved to backend {backend.name!r} "
                           f"(interpret={getattr(backend, 'interpret', False)}), "
                           f"the cell asks for {want!r} compiled for the chip")
    w0 = init(key)
    program = checked_rounds(runtime, source, w0)
    del w0
    step_cache = runtime.scheduler._round_step
    cache0 = step_cache._cache_size() if hasattr(step_cache, "_cache_size") else 0
    compiles = CompileCounter()
    setup_s = time.time() - setup_t0

    out = {"setup_s": setup_s, "checked_losses": program["losses"]}
    with compiles:
        if trace:
            from .common import traced

            (rounds, window_s), red = traced(
                lambda: run_window(runtime, source, min(seconds, TRACE_SECONDS), spans=True))
            out["reduction"] = red
        else:
            rounds, window_s = run_window(runtime, source, seconds)
    cache1 = step_cache._cache_size() if hasattr(step_cache, "_cache_size") else 0
    out.update(rounds=rounds, window_s=window_s, round_s=window_s / rounds,
               attempted=rounds, failed=0,
               compiles_in_window=compiles.count + (cache1 - cache0),
               costs=costs(cell, runtime))
    info(f"window: {rounds} rounds in {window_s:.4f} s; compiles in window "
         f"{out['compiles_in_window']}")
    out["memory_peak_bytes"] = cell.memory_peak()

    fed, algo = cell.traffic["federation"], cell.config["training"]
    del runtime, step_cache
    gc.collect()
    t = time.perf_counter()
    ref = reference.follow(
        cell.ref, cell.config, init(key), source, clients=fed["clients"],
        clusters=fed["clusters"], tau1=algo["tau1"], tau2=algo["tau2"], alpha=algo["alpha"],
        lr=algo["learning_rate"], topology=algo["topology"], rounds=CHECKED_ROUNDS)
    cmp = reference.compare(program, ref)
    info(f"reference: {time.perf_counter() - t:.1f} s; program losses {program['losses']}; "
         f"reference losses {ref['losses'].tolist()}; worst leaves {cmp['worst_leaf']}; "
         f"left out {cmp['left_out']}")
    out["numbers"] = cmp["numbers"]
    return out
