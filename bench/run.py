"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration, traffic
mix and metrics; their files are found by name under ``bench/``.  Without
a TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.  Set-up (start-up, inputs, weights, compilation,
the checked first steps) is timed from the start of the process; then the
window runs for ``--seconds``.  With ``--trace 1`` the window is traced
and the per-layer metrics are read from the trace.  The last line of
standard output is one JSON object; the numbers compared with the plain
reference, each beside its limit, end standard error and the line.
"""
from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def process_start() -> float:
    """Wall-clock time at which this process started."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        ticks = os.sysconf("SC_CLK_TCK")
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - (uptime - int(fields[19]) / ticks)
    except (OSError, ValueError, IndexError):
        return time.time()


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(workload: str):
    """The cell, with the checkout's program importable and its compile
    cache on.  Exits non-zero where the checkout holds no program."""
    sys.path.insert(0, str(ROOT))
    from bench.common import Cell

    cell = Cell(workload)
    src = ROOT / "src"
    if not (src / "repro" / "core").is_dir():
        print(f"bench: no program under {src}; no result", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, str(src))
    import jax
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    # keep every program, however quick to compile, so set-up is the same each run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell


def result_line(cell, out: dict, devices, trace: bool) -> dict:
    from bench.peaks import peaks_for

    limits = cell.limits["limits"]
    checks = {k: {"value": out["numbers"][k], "limit": limits[k]} for k in limits}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    line = {"correct": correct, "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        red = out["reduction"]
        ctx = dict(out, peaks=peaks_for(devices[0].device_kind), chips=len(devices))
        for name, reader in cell.readers.items():
            value = reader.read(ctx)
            if value is None:  # the cell lists this metric: its reader should find it
                print(f"bench: per-layer metric {name} found nothing to read in this "
                      "cell's trace; left out of the line", file=sys.stderr)
                continue
            unit = next(m["unit"] for m in cell.per_layer if m["name"] == name)
            metrics[name] = {"value": value, "unit": unit}
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:  # what the readers read besides the trace, for the harness's tests
            kept = {k: v for k, v in ctx.items() if k not in ("reduction", "numbers")}
            Path(keep + ".ctx.json").write_text(json.dumps(
                {"workload": cell.name, "ctx": kept, "metrics": metrics}, default=float))
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out[m["name"]], "unit": m["unit"]}
    line.update(metrics=metrics, device=device, checks=checks)
    return line


def main(argv=None) -> None:
    t0 = process_start()
    args = parse(argv)
    cell = prepare(args.workload)
    from bench.common import info, require_chips

    devices = require_chips(cell.chips)
    kind = importlib.import_module(f"bench.{cell.traffic['kind']}")
    out = kind.run(cell, args.seed, args.seconds, bool(args.trace), t0)
    line = result_line(cell, out, devices, bool(args.trace))
    info(f"peak device memory {out['memory_peak_bytes']} B")
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
