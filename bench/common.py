"""What every cell shares: finding its files by name, the chip check,
compile counting, tracing, and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"


def info(msg: str) -> None:
    print(f"info {msg}", file=sys.stderr, flush=True)


def load_module(path: Path):
    """A benchmark file by path (its name may hold '-' and '.')."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path.relative_to(ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path.relative_to(ROOT)} is missing")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return read_json(ROOT / "BENCHMARK.json")


class Cell:
    """One entry of ``workloads`` with every file it names, found by name:
    ``configs/<config>.json`` and its reference ``configs/<config>.py``,
    ``traffic/<traffic>.json`` and the generator it names,
    ``generators/<generator>.py``, ``limits/<cell>.json``, the model file
    ``models/<model>.py`` (the program's model built from the sizes, and
    the model's FLOP counts) and ``metrics/<metric>.py`` for each per-layer
    metric the cell reports."""

    def __init__(self, name: str, bench: dict | None = None):
        bench = benchmark() if bench is None else bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        self.config = read_json(BENCH / "configs" / f"{self.entry['config']}.json")
        self.ref = load_module(BENCH / "configs" / f"{self.entry['config']}.py")
        self.traffic = read_json(BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = read_json(BENCH / "limits" / f"{name}.json")
        self.generator = load_module(BENCH / "generators" / f"{self.traffic['generator']}.py")
        self.program_model = load_module(BENCH / "models" / f"{self.config['model']}.py")
        self.end_to_end = [m for m in bench["end_to_end"] if _reports(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
        self.readers = {m["name"]: load_module(BENCH / "metrics" / f"{m['name']}.py")
                        for m in self.per_layer}

    def inputs(self, seed: int, window_s: float | None = None, traffic: dict | None = None):
        """What the traffic file's generator makes from the seed: a training
        batch source, or a serving cell's requests over ``window_s``."""
        return self.generator.make(seed, self.config, traffic or self.traffic, window_s)

    def memory_peak(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[: self.chips]]
        return int(max(peaks))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def require_chips(count: int):
    """The devices, or exit non-zero with no result where JAX finds no TPU
    or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, but JAX found platform {devices[0].platform!r} "
              f"({devices[0].device_kind}); no result", file=sys.stderr)
        sys.exit(2)
    if len(devices) < count:
        print(f"bench: the cell needs {count} TPU chips, JAX found {len(devices)}; "
              "no result", file=sys.stderr)
        sys.exit(2)
    return devices[:count]


class CompileCounter:
    """Counts XLA compilations (cache hits excluded) while it is entered."""

    def __init__(self):
        self.count = 0
        self._on = False
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_kw) -> None:
        if self._on and "backend_compile" in event:
            self.count += 1

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


def traced(fn):
    """Run ``fn`` under the profiler; returns (fn's result, reduction)."""
    import jax

    from . import trace

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
        result = fn()
    tr = trace.compact(str(TRACE_DIR))
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:  # the compact trace, and the profiler's own file beside it
        trace.save(tr, keep)
        for path in TRACE_DIR.rglob("*.xplane.pb"):
            shutil.copy(path, keep + ".xplane.pb")
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return result, trace.reduce(tr)
