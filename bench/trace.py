"""Reduction of a profiler trace to the benchmark's per-layer numbers.

A traced run writes an ``.xplane.pb`` with ``jax.profiler``.  ``compact``
keeps what the reduction reads: every line of each device plane and the
benchmark's own host spans (``bench.*``), as ``[name, start_ns, dur_ns]``
events on the trace's clock.  Everything below works on that compact form,
so a small recorded trace under ``bench/testdata/`` checks it on the CPU.

The traced window is the host span ``bench.window``.  Device time is read
from the device planes' ``XLA Ops`` line, where each event is one operation
the chip ran.
"""
from __future__ import annotations

import glob
import gzip
import json
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_WORDS = ("collective-permute", "all-reduce", "all-gather",
                    "reduce-scatter", "all-to-all")


def compact(profile_dir: str) -> dict:
    """Read the one ``.xplane.pb`` under ``profile_dir`` into compact form."""
    import jax

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_dir}, found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)] for e in line.events
                      if device or e.name.startswith("bench.")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def save(tr: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tr, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def host_spans(tr: dict) -> list:
    return [e for p in tr["planes"] if not p["name"].startswith("/device:")
            for line in p["lines"] for e in line["events"]]


def window(tr: dict) -> tuple[int, int]:
    spans = [e for e in host_spans(tr) if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
    _, start, dur = spans[0]
    return start, start + dur


def device_ops(tr: dict, line_name: str = OPS_LINE, kind: str = "TPU") -> dict:
    """``{plane name: [[name, start_ns, dur_ns], ...]}`` of one line of each
    chip's plane: its operations, or with ``MODULES_LINE`` its programs."""
    out = {}
    for p in tr["planes"]:
        if p["name"].startswith(f"/device:{kind}:") and "/" not in p["name"][len("/device:"):]:
            for line in p["lines"]:
                if line["name"] == line_name:
                    out[p["name"]] = line["events"]
    return out


def _clip(events, t0: int, t1: int):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def _union(intervals) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events, t0: int, t1: int) -> int:
    return sum(b - a for a, b in _union((a, b) for _, a, b in _clip(events, t0, t1)))


def time_by_op(events, t0: int, t1: int) -> dict:
    out: dict = {}
    for name, a, b in _clip(events, t0, t1):
        out[name] = out.get(name, 0) + (b - a)
    return out


def matching_ns(events, t0: int, t1: int, words) -> tuple[int, int]:
    """(total ns, calls) of the ops whose name contains any of ``words``."""
    total = calls = 0
    for name, a, b in _clip(events, t0, t1):
        if any(w in name for w in words):
            total += b - a
            calls += 1
    return total, calls


def exposed_collective_ns(events, t0: int, t1: int) -> int:
    """Collective time on one chip during which no other op runs there."""
    coll, other = [], []
    for name, a, b in _clip(events, t0, t1):
        (coll if any(w in name for w in COLLECTIVE_WORDS) else other).append((a, b))
    compute = _union(other)
    exposed = 0
    for a, b in _union(coll):
        covered = sum(max(0, min(b, y) - max(a, x)) for x, y in compute)
        exposed += (b - a) - covered
    return exposed


def idle_gaps(events, spans, t0: int, t1: int, top: int = 10) -> list:
    """The longest stretches of the window with no op on the chip, each
    named by the innermost benchmark host span that covers its middle."""
    gaps, cursor = [], t0
    for a, b in _union((a, b) for _, a, b in _clip(events, t0, t1)):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < t1:
        gaps.append((cursor, t1))
    named = []
    for a, b in gaps:
        mid = (a + b) // 2
        cover = [e for e in spans if e[1] <= mid < e[1] + e[2] and e[0] != WINDOW_SPAN]
        name = min(cover, key=lambda e: e[2])[0] if cover else "bench.window (no span)"
        named.append([name, (b - a) / 1e9])
    named.sort(key=lambda g: -g[1])
    return named[:top]


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy and window seconds averaged over chips, device time by op summed
    over chips, and the longest idle gaps, all inside the traced window."""
    t0, t1 = window(tr)
    per_chip = device_ops(tr)
    if not per_chip:
        raise ValueError(f"no device plane with a {OPS_LINE!r} line in the trace")
    spans = host_spans(tr)
    by_op: dict = {}
    busy, gaps = [], []
    for events in per_chip.values():
        busy.append(busy_ns(events, t0, t1))
        for k, v in time_by_op(events, t0, t1).items():
            by_op[k] = by_op.get(k, 0) + v
        gaps.extend(idle_gaps(events, spans, t0, t1, top))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "chips": len(per_chip),
        "device_ops": [[k, v / 1e9] for k, v in ops[:top]],
        "idle_gaps": gaps[:top],
        "t0": t0,
        "t1": t1,
        "per_chip": per_chip,
        "modules": device_ops(tr, MODULES_LINE),
    }
