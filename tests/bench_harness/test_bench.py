"""CPU tests of the benchmark harness: counters and peaks, trace reduction,
the input generators, refusal without a chip, and discovery of every
cell's files.

    JAX_PLATFORMS=cpu python3 -m pytest -q tests/bench_harness
"""
from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import counters, trace  # noqa: E402
from bench.common import Cell, benchmark, load_module, read_json  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402
from test_bench_correctness import with_serving  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"
GRANITE = read_json(ROOT / "bench" / "configs" / "granite-8b-1l.json")
LM = load_module(ROOT / "bench" / "models" / "causal-lm.py")
OPEN_LOOP = load_module(ROOT / "bench" / "generators" / "open_loop.py")
MARKOV = load_module(ROOT / "bench" / "generators" / "clustered_markov.py")


# -- counters and peaks --------------------------------------------------------

def test_granite_flops_per_token_match_an_independent_count():
    """Matmul weights counted from the program's granite-8b leaf shapes,
    plus causal attention, against the model file fed the config file."""
    import dataclasses

    import jax
    from repro.configs import get_config
    from repro.models import CausalLM

    arch = dataclasses.replace(get_config("granite-8b"), num_layers=1)
    shapes = jax.eval_shape(CausalLM(arch).init, jax.random.PRNGKey(0))
    matmul = sum(math.prod(x.shape) for path, x in
                 jax.tree_util.tree_flatten_with_path(shapes)[0]
                 if x.ndim >= 2 and not any(w in jax.tree_util.keystr(path)
                                            for w in ("embed", "ln_")))
    seq = 1024
    attn = arch.num_layers * 4 * arch.num_heads * arch.head_dim * (seq + 1) / 2
    per_token = LM.forward_flops_per_token(GRANITE, LM.causal_keys(seq))
    assert per_token == pytest.approx(2 * matmul + attn)
    example = LM.train_flops_per_example(GRANITE, {"seq_len": seq, "batch": 1})
    assert example == pytest.approx(3 * seq * (2 * matmul + attn))
    # 6 x 419.4 M weights plus about 1% for attention, per token
    assert example / seq / 1e9 == pytest.approx(2.541, abs=0.01)


def test_serving_flops_take_attention_at_each_phase_context():
    reqs = [{"prompt": np.zeros(100), "budget": 10}, {"prompt": np.zeros(300), "budget": 30}]
    got = LM.serve_flops(GRANITE, reqs)
    assert got["prefill_flops_per_token"] == LM.forward_flops_per_token(GRANITE, 100.5)
    assert got["decode_flops_per_token"] == LM.forward_flops_per_token(GRANITE, 200 + 10)
    per_key = 4 * GRANITE["num_heads"] * GRANITE["head_dim"]
    assert (LM.forward_flops_per_token(GRANITE, 11) - LM.forward_flops_per_token(GRANITE, 1)
            == 10 * per_key)
    assert LM.serve_flops(GRANITE, [])["decode_flops_per_token"] == 0.0


def test_transition_and_sgd_bytes_follow_leaf_shapes():
    shapes = [(4, 4096, 14336), (4, 4096)]
    n = 4096 * 14336 + 4096  # positions per client
    flops, bytes_ = counters.transition_cost(shapes, 2, clients=4, clusters=2, alpha=1)
    assert bytes_ == 2 * 4 * n * 2  # every client's leaf read and written once, bf16
    assert flops == n * (4 * 4 * 2 + 2 * 1 * 2 * 2)
    flops, bytes_ = counters.sgd_cost(shapes, 2, 2)
    assert bytes_ == 4 * n * (2 + 2 + 2) and flops == 2 * 4 * n
    share = counters.roofline_share(0.0, 819e9, 2.0, peaks_for("TPU v5 lite"))
    assert share == pytest.approx(50.0)


def test_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("cpu")


# -- trace reduction --------------------------------------------------------------

def synthetic_trace():
    ms = 1_000_000
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench.window", 0, 100 * ms], ["bench.dispatch", 0, 15 * ms],
            ["bench.wait", 15 * ms, 85 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_round_step", 20 * ms, 70 * ms]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 20 * ms, 30 * ms],
                ["fused_transition_kernel", 50 * ms, 10 * ms],
                ["collective-permute.3", 55 * ms, 20 * ms],
                ["_sgd_kernel", 80 * ms, 10 * ms],
                ["fusion.1", 95 * ms, 20 * ms]]}]},
    ]}


def test_reduction_on_a_synthetic_trace():
    tr = synthetic_trace()
    red = trace.reduce(tr)
    ms = 1e-3
    assert red["window_s"] == pytest.approx(100 * ms)
    # busy: [20, 75] and [80, 90] and [95, 100] clipped to the window
    assert red["busy_s"] == pytest.approx(70 * ms)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(35 * ms)
    # idle: [0, 20] under the dispatch span, [75, 80] and [90, 95] under the wait
    assert red["idle_gaps"] == [["bench.dispatch", pytest.approx(20 * ms)],
                                ["bench.wait", pytest.approx(5 * ms)],
                                ["bench.wait", pytest.approx(5 * ms)]]
    events = red["per_chip"]["/device:TPU:0"]
    assert trace.exposed_collective_ns(events, red["t0"], red["t1"]) == 15 * 1_000_000
    assert trace.matching_ns(events, red["t0"], red["t1"], ("_sgd_kernel",)) == (10_000_000, 1)
    assert trace.matching_ns(red["modules"]["/device:TPU:0"], red["t0"], red["t1"],
                             ("round_step",)) == (70_000_000, 1)


def recorded_traces():
    return sorted(TESTDATA.glob("*.json.gz"))


@pytest.mark.parametrize("path", recorded_traces(), ids=lambda p: p.name)
def test_readers_find_their_metrics_in_a_trace_recorded_on_the_chip(path):
    """A traced run's compact trace, kept with what its readers read
    besides it (``BENCH_KEEP_TRACE``): every per-layer metric its cell lists
    finds the program's kernel and program names, and reads as in the run."""
    assert path.stat().st_size < 1_000_000
    kept = read_json(Path(f"{path}.ctx.json"))
    red = trace.reduce(trace.load(str(path)))
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["device_ops"] and red["idle_gaps"]
    cell = Cell(kept["workload"])
    ctx = dict(kept["ctx"], reduction=red)
    units = {m["name"]: m["unit"] for m in cell.per_layer}
    for name, reader in cell.readers.items():
        value = reader.read(ctx)
        assert value is not None, name
        assert value == pytest.approx(kept["metrics"][name], rel=1e-9), name
        if units[name] == "%":
            assert 0 < value <= 105, name


@pytest.fixture(scope="module")
def described_chip():
    """One chip of a described, not attached, TPU v5e: the program compiles
    for it without a chip."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the cache but cannot be read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def test_kernel_readers_name_ops_the_program_compiles_for_the_chip(described_chip):
    """The trace's op names are the compiled program's instruction names:
    each kernel reader's words name a custom call of its kernel."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.core.backends import PallasBackend
    from repro.core.protocol import ClusterSpec
    from repro.kernels import sgd_update_tree

    w = jax.ShapeDtypeStruct((4, 1, 512, 1024), jnp.bfloat16, sharding=described_chip)
    backend = PallasBackend(ClusterSpec.uniform(4, 2), np.full((2, 2), 0.5), 1,
                            interpret=False)
    programs = {
        "fused_sgd_roofline": (lambda w, g: sgd_update_tree({"w": w}, {"w": g}, 0.01), (w, w)),
        "fused_transition_roofline": (lambda w: backend.transition({"w": w}, "inter"), (w,)),
    }
    for metric, (fn, args) in programs.items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        calls = re.findall(r"%(\S+) = .* custom-call\(.*tpu_custom_call", text)
        words = load_module(ROOT / "bench" / "metrics" / f"{metric}.py").KERNEL
        assert calls and all(any(word in c for word in words) for c in calls), (metric, calls)


def test_serving_readers_name_the_programs_modules():
    """A jitted program's XLA module is ``jit_<function name>``."""
    from repro.serving.slots import build_slot_programs

    prefill, admit, chunk = build_slot_programs(
        LM.build(dict(GRANITE, d_model=64, d_ff=128, num_heads=4, num_kv_heads=2,
                      head_dim=16, vocab_size=512)),
        temperature=0.0, gen_cap=8, chunk_steps=4, stacked=True)
    chunk_words = load_module(ROOT / "bench" / "metrics" / "decode_chunk_ms.py").PROGRAM
    pa = load_module(ROOT / "bench" / "metrics" / "prefill_admit_ms.py")
    for fn, words in ((chunk, chunk_words), (prefill, pa.PREFILL), (admit, pa.ADMIT)):
        assert any(w in f"jit_{fn.__name__}" for w in words), (fn.__name__, words)
    assert not any(w in f"jit_{prefill.__name__}" for w in chunk_words + pa.ADMIT)


# -- generators -------------------------------------------------------------------

def markov_traffic(**params):
    return {"federation": {"clients": 4, "clusters": 2},
            "params": dict(seq_len=64, batch=2, pool=8, noise=0.05, **params)}


def test_clustered_markov_is_deterministic_and_follows_its_cluster():
    cfg, traffic = {"vocab_size": 97}, markov_traffic()
    src_a, tok_a = MARKOV.make(2**31 + 5, cfg, traffic)
    src_b, tok_b = MARKOV.make(2**31 + 5, cfg, traffic)
    _, tok_c = MARKOV.make(2**31 + 6, cfg, traffic)
    assert np.array_equal(tok_a, tok_b) and not np.array_equal(tok_a, tok_c)
    b = src_a(3)
    assert b["tokens"].shape == (4, 2, 64) and np.array_equal(b["labels"][:, :, :-1],
                                                              b["tokens"][:, :, 1:])
    # rows of the first pool // batch iterations all differ
    rows = np.stack([src_a(k)["tokens"] for k in range(1, 5)], 1).reshape(4, 8, 64)
    assert all(len({r.tobytes() for r in rows[c]}) == 8 for c in range(4))
    # clients of one cluster share a successor table; about 1 - noise follow it
    succ = {}
    for c in (0, 1):
        for s in tok_a[c]:
            for x, y in zip(s[:-1], s[1:]):
                succ.setdefault(int(x), []).append(int(y))
    agree = np.mean([max(set(v), key=v.count) == y for v in succ.values() for y in v])
    assert agree > 0.9


def serve_traffic(**params):
    return {"clusters": 2, "params": dict(
        rate_per_s=10.0, prompt={"lo": 64, "hi": 1024, "exponent": 1.1},
        budget={"lo": 1, "hi": 128, "exponent": 1.1}, **params)}


def test_open_loop_offers_the_same_work_for_every_seed():
    cfg, traffic = {"vocab_size": 512}, serve_traffic()
    a = OPEN_LOOP.make(2**31 + 3, cfg, traffic, 15.0)
    b = OPEN_LOOP.make(2**31 + 3, cfg, traffic, 15.0)
    c = OPEN_LOOP.make(2**31 + 4, cfg, traffic, 15.0)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(r["prompt"], s["prompt"]) for r, s in zip(a, b))
    assert [r["due_s"] for r in a] != [r["due_s"] for r in c]
    assert len(a) == 300
    for key in ("budget", "cluster"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in c)
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    # the window holds the same requests, in number and sizes, for every seed
    for reqs in (a, c):
        inside = [r for r in reqs if r["due_s"] < 15.0]
        assert len(inside) == 150 and inside[0]["due_s"] == 0.0
        assert sorted(r["budget"] for r in inside) == sorted(r["budget"] for r in a[:150])
    lens = np.array([len(r["prompt"]) for r in a])
    budgets = np.array([r["budget"] for r in a])
    assert lens.min() >= 64 and lens.max() <= 1024 and budgets.min() >= 1 and budgets.max() <= 128
    # heavy tail: the median sits far below the mean of the range
    assert np.median(budgets) < 20 and np.median(lens) < 400
    gaps = np.diff([0.0] + [r["due_s"] for r in a])
    assert np.mean(gaps) == pytest.approx(0.1, rel=0.05)
    assert sum(r["cluster"] for r in a) == 150


def test_open_loop_bursts_keep_the_window_and_fall_in_on_stretches():
    cfg = {"vocab_size": 512}
    steady = OPEN_LOOP.make(2**31 + 7, cfg, serve_traffic(), 12.0)
    bursty = OPEN_LOOP.make(2**31 + 7, cfg, serve_traffic(bursts={"on_s": 1.0, "off_s": 2.0}),
                            12.0)
    inside = [r for r in bursty if r["due_s"] < 12.0]
    assert len(inside) == 120
    assert sorted(r["budget"] for r in inside) == sorted(r["budget"] for r in steady[:120])
    # every arrival lies in the first second of a three-second period
    assert all((r["due_s"] % 3.0) < 1.0 + 1e-9 for r in bursty)
    assert max(r["due_s"] for r in inside) > 9.0


# -- refusal and discovery -----------------------------------------------------------

def run_bench(cwd: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu_before_building_a_model():
    p = run_bench(ROOT, "--workload", "train.granite8b.seq1024", "--seed", str(2**31 + 1),
                  "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "no result" in p.stderr
    assert p.stdout.strip() == ""
    assert "info" not in p.stderr  # nothing was built or timed


def test_refuses_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path, "--workload", "train.granite8b.seq1024", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("name", [w["name"] for w in with_serving()["workloads"]])
def test_every_cell_finds_its_files_by_name(name):
    cell = Cell(name, with_serving())
    assert cell.entry["config"] == cell.config["name"]
    assert hasattr(cell.ref, "loss") and hasattr(cell.ref, "init")
    assert hasattr(cell.program_model, "build")
    assert callable(cell.generator.make)
    assert cell.traffic["kind"] in ("train", "serve")
    importlib.import_module(f"bench.{cell.traffic['kind']}")
    assert cell.limits["limits"]
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    if name in {w["name"] for w in benchmark()["workloads"]}:
        assert len(cell.end_to_end) >= 2 and cell.readers
    for reader in cell.readers.values():
        assert callable(reader.read)


def test_benchmark_names_only_files_that_exist():
    bench = benchmark()
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_granite_config_keeps_every_published_width():
    from repro.configs import get_config

    cfg = read_json(ROOT / "bench" / "configs" / "granite-8b-1l.json")
    arch = get_config("granite-8b")
    for key in ("d_model", "d_ff", "vocab_size", "num_heads", "num_kv_heads", "head_dim",
                "rope_theta", "norm_eps", "tie_embeddings", "dtype", "remat"):
        assert cfg[key] == getattr(arch, key), key
    assert cfg["num_layers"] == 1 and cfg["published"]["num_layers"] == arch.num_layers
    entry = next(c for c in benchmark()["configs"] if c["name"] == "granite-8b-1l")
    assert entry["reduced"] == ["num_layers"]


def test_reference_layouts_match_the_program_models():
    import jax

    for name in [c["name"] for c in benchmark()["configs"]]:
        cfg = read_json(ROOT / "bench" / "configs" / f"{name}.json")
        ref = load_module(ROOT / "bench" / "configs" / f"{name}.py")
        model = load_module(ROOT / "bench" / "models" / f"{cfg['model']}.py").build(cfg)
        want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda k: ref.init(k, cfg), jax.random.PRNGKey(0))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.shape == b.shape and a.dtype == b.dtype


def test_checks_end_the_result_line():
    """The compared numbers, each with its limit, are the line's last key."""
    from bench.run import result_line

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    cell = Cell("train.granite8b.seq1024")
    numbers = {k: 0.0 for k in cell.limits["limits"]}
    out = {"numbers": numbers, "memory_peak_bytes": 1, "attempted": 3, "failed": 0,
           "setup_s": 1.0, "round_s": 0.5}
    line = result_line(cell, out, [Dev()], trace=False)
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "round_s"}
    json.dumps(line)
