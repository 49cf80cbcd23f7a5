"""Where a training cell's round goes, by the program's own layer names.

The round step names its layers with ``jax.named_scope`` (``repro.spans``):
``sdfeel.local_update`` > ``forward_backward`` > ``embed``, ``attention``,
``mlp``, ``lm_head``; ``local_update`` > ``optimizer``;
``transition.intra``, ``transition.inter``.  The scheduler's step adds two
host spans, ``sdfeel.stage`` (its batches) and ``sdfeel.dispatch`` (the
compiled call).

A TPU's ``XLA Ops`` trace events carry only an op's HLO text, without its
``op_name``, so the scope path of each op is read from the round step's
compiled HLO (``scopes_from_hlo``).  A layer's device time is the union of
the intervals of the ops whose path holds its scope, so a ``while`` op and
the body ops it wraps count once.

    python3 bench/layers.py --workload <cell> --seed <n> [--keep <path>]

runs the cell's round on the chip, traces a window as the benchmark's
traced run does (``bench.common.traced``, ``bench.train.TRACE_SECONDS``)
and prints, per round: device ms under each scope, under none and busy;
host ms in each span; and the longest idle gaps, each named by the
innermost host span around it.  ``--keep`` saves that window's compact
trace, with the ``sdfeel.`` host spans and a ``"scopes"`` map ``{op name:
op_name path}`` beside the benchmark's own (``bench/trace.py``), for
``python3 bench/layers.py --kept <path>``.  Without a TPU it exits
non-zero and prints nothing.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __package__ in (None, ""):  # run as a script
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
from bench import trace  # noqa: E402
from repro import spans  # noqa: E402

# outside bench.common.TRACE_DIR, which ``traced`` removes
KEEP_DIR = ROOT / ".bench_layers"


def scopes_from_hlo(text: str) -> dict:
    """``{instruction name: op_name path}`` of a compiled program's HLO text."""
    return dict(re.findall(r'^\s*(?:ROOT )?%(\S+) = .*metadata=\{op_name="([^"]*)"', text,
                           re.MULTILINE))


def instruction(op: str) -> str:
    """The instruction name of an ``XLA Ops`` event, whose name is its HLO
    text on the TPU (``%while.636 = (s32[], ...) while(...)``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def op_scopes(events, paths: dict) -> dict:
    """``{op name: op_name path}`` for the ops of ``events`` that the
    compiled program names."""
    out = {}
    for name, _, _ in events:
        path = paths.get(instruction(name))
        if path is not None:
            out[name] = path
    return out


def scoped_ns(events, scopes: dict, t0: int, t1: int, words) -> int:
    """Union of the intervals, clipped to [t0, t1), of the ops whose scope
    path holds any of ``words``."""
    return trace.busy_ns([e for e in events if any(w in scopes.get(e[0], "") for w in words)],
                         t0, t1)


def unscoped_by_op(events, scopes: dict, t0: int, t1: int) -> dict:
    """ns of each op outside every scope during which no scoped op runs: a
    ``while`` with no scope of its own counts only where its scoped body
    does not."""
    scoped = trace._union((a, b) for name, a, b in trace._clip(events, t0, t1)
                          if spans.PREFIX in scopes.get(name, ""))
    starts = [a for a, _ in scoped]
    out: dict = {}
    for name, a, b in trace._clip(events, t0, t1):
        if spans.PREFIX in scopes.get(name, ""):
            continue
        covered, i = 0, max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(scoped) and scoped[i][0] < b:
            covered += max(0, min(b, scoped[i][1]) - max(a, scoped[i][0]))
            i += 1
        out[name] = out.get(name, 0) + (b - a) - covered
    return out


def breakdown(tr: dict, rounds: int) -> dict:
    """Per round, averaged over the chips: device ms under each scope, under
    none (``unscoped``) and busy; host ms in each program span; the longest
    idle gaps; the ops outside every scope that take the most time."""
    red = trace.reduce(tr)
    scopes, t0, t1 = tr.get("scopes", {}), red["t0"], red["t1"]
    chips = list(red["per_chip"].values())
    device = {scope: sum(scoped_ns(ev, scopes, t0, t1, (scope,)) for ev in chips)
              for scope in spans.SCOPES}
    busy = sum(trace.busy_ns(ev, t0, t1) for ev in chips)
    device["unscoped"] = busy - sum(scoped_ns(ev, scopes, t0, t1, (spans.PREFIX,))
                                    for ev in chips)
    device["busy"] = busy
    host = {span: sum(max(0, min(a + d, t1) - max(a, t0)) for n, a, d in trace.host_spans(tr)
                      if n == span) for span in spans.SPANS}
    rest: dict = {}
    for ev in chips:
        for name, ns in unscoped_by_op(ev, scopes, t0, t1).items():
            rest[name] = rest.get(name, 0) + ns
    per = len(chips) * rounds * 1e6
    return {
        "rounds": rounds,
        "window_s": red["window_s"],
        "device_ms": {k: v / per for k, v in device.items()},
        "host_ms": {k: v / rounds / 1e6 for k, v in host.items()},
        "idle_gaps": red["idle_gaps"],
        "largest_unscoped_s": [[k[:160], v / 1e9] for k, v in
                               sorted(rest.items(), key=lambda kv: -kv[1])[:5]],
    }


def program_spans(xplane: str) -> list:
    """The ``sdfeel.`` host spans of a profiler's ``.xplane.pb`` file."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane)
    return [[e.name, int(e.start_ns), int(e.duration_ns)] for p in data.planes
            if not p.name.startswith("/device:") for line in p.lines for e in line.events
            if e.name.startswith(spans.PREFIX)]


def round_step_scopes(sched, source) -> dict:
    """``{instruction name: op_name path}`` of the round step the scheduler
    runs, lowered from the state it holds and a batch staged as its step
    stages one (the resident path, which the training cells take); the
    same program, so a compile-cache hit."""
    from repro.core.pipeline import device_batch, stack_window

    batch = device_batch(stack_window(source, 1, sched.iterations_per_step))
    step = sched._round_step.lower(sched.params, sched.opt_state, batch)
    return scopes_from_hlo(step.compile().as_text())


def record(cell, seed: int) -> dict:
    """Build the cell's runtime, warm it up and trace a window of its rounds
    through the benchmark's own ``traced``; the compact trace it keeps, with
    the program's host spans, the scopes of its ops and the rounds in it."""
    from bench.common import traced
    from bench.train import TRACE_SECONDS, build, run_window

    runtime, source, _, _ = build(cell, seed)
    for _ in range(3):  # compiles, and fills the batch pipeline
        runtime.step(source).losses.block_until_ready()

    shutil.rmtree(KEEP_DIR, ignore_errors=True)
    KEEP_DIR.mkdir(parents=True)
    keep, was = str(KEEP_DIR / "window.gz"), os.environ.get("BENCH_KEEP_TRACE")
    os.environ["BENCH_KEEP_TRACE"] = keep  # traced keeps the window and its .xplane.pb
    try:
        (rounds, _), _ = traced(lambda: run_window(runtime, source, TRACE_SECONDS, spans=True))
    finally:
        if was is None:
            del os.environ["BENCH_KEEP_TRACE"]
        else:
            os.environ["BENCH_KEEP_TRACE"] = was
    tr = trace.load(keep)
    read = (trace.OPS_LINE, trace.MODULES_LINE)  # all the breakdown reads: a small window
    for p in tr["planes"]:
        if p["name"].startswith("/device:"):
            p["lines"] = [x for x in p["lines"] if x["name"] in read]
    host = next(p for p in tr["planes"] if not p["name"].startswith("/device:"))
    host["lines"].append({"name": "program spans", "events": program_spans(keep + ".xplane.pb")})
    shutil.rmtree(KEEP_DIR, ignore_errors=True)
    paths = round_step_scopes(runtime.scheduler, source)  # outside the window
    tr["scopes"] = {}
    for events in trace.device_ops(tr).values():
        tr["scopes"].update(op_scopes(events, paths))
    tr["rounds"] = rounds
    return tr


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--keep", help="save the traced window here (<name>.layers.gz)")
    ap.add_argument("--kept", help="reduce a window saved with --keep instead of running")
    args = ap.parse_args(argv)
    if args.kept:
        tr = trace.load(args.kept)
        print(json.dumps(breakdown(tr, tr["rounds"]), indent=1))
        return
    from bench.run import prepare

    cell = prepare(args.workload)
    from bench.common import info, require_chips

    devices = require_chips(cell.chips)
    t = time.perf_counter()
    tr = record(cell, args.seed)
    info(f"traced {tr['rounds']} rounds; set-up and trace {time.perf_counter() - t:.1f} s")
    if args.keep:
        trace.save(tr, args.keep)
    out = dict(breakdown(tr, tr["rounds"]), workload=cell.name, device=devices[0].device_kind)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
