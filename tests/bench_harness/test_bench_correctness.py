"""The comparison that decides ``correct``, at a size a CPU test run holds.

Each cell runs as ``bench/run.py`` runs it, minus the look for a chip, with
its widths cut down: the program with a fault planted underneath the
harness has to come out not correct, and so has the control (the
configuration's reference one precision step lower, in the program's
place), while the program as it stands passes.  The limits are the cells'
own, from ``bench/limits/``.

    JAX_PLATFORMS=cpu python3 -m pytest -q tests/bench_harness
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import serve, train  # noqa: E402
from bench.common import Cell, benchmark  # noqa: E402
from bench.run import result_line  # noqa: E402

SEED = 2**31 + 11
# The serving cell joins BENCHMARK.json once its knee has been swept on the
# chip (bench/sweep.py); its files are in place, and its comparison is
# tested here from this entry until then.
SERVE = {"name": "serve.granite8b.steady", "config": "granite-8b-1l", "traffic": "steady",
         "chips": 1, "why": "chat on one edge server's personalised model"}


def with_serving() -> dict:
    """BENCHMARK.json, with the serving cell's entry where it has none."""
    bench = benchmark()
    if SERVE["name"] not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"] = bench["workloads"] + [SERVE]
    return bench


def small(name: str) -> Cell:
    """The cell at test size; on the CPU the round step resolves to dense."""
    cell = Cell(name, with_serving())
    cell.config.update(d_model=64, d_ff=128, vocab_size=512, num_heads=4,
                       num_kv_heads=2, head_dim=16)
    if cell.traffic["kind"] == "train":
        cell.traffic["federation"]["backend"] = "dense"
        cell.traffic["params"].update(seq_len=32, pool=16)
    else:
        cell.traffic["params"].update(rate_per_s=20.0,
                                      prompt={"lo": 8, "hi": 64, "exponent": 1.1},
                                      budget={"lo": 1, "hi": 16, "exponent": 1.1})
        cell.traffic["server"].update(buckets=[16, 32, 64], gen_cap=16, max_batch=4)
    return cell


def run_cell(cell: Cell, fault=None) -> dict:
    kind = train if cell.traffic["kind"] == "train" else serve
    out = kind.run(cell, SEED, 1.0, False, time.time(), fault=fault)
    return result_line(cell, out, jax.devices()[:1], trace=False)


TRAIN = ["train.granite8b.seq1024"]
CASES = [(c, f) for c in TRAIN for f in (None, "unchanged", "half_batch", "no_transition")]
CASES += [("serve.granite8b.steady", None), ("serve.granite8b.steady", "altered_token")]


@pytest.mark.parametrize("name,fault", CASES, ids=lambda v: str(v))
def test_a_planted_fault_comes_out_not_correct(name, fault):
    line = run_cell(small(name), fault)
    assert line["correct"] is (fault is None), line["checks"]
    assert line["failed"] == 0


@pytest.mark.parametrize("name", TRAIN)
def test_the_control_fails_a_limit(name):
    cell = small(name)
    got = train.readings(cell, SEED, program=False)["control"]
    limits = cell.limits["limits"]
    assert any(got[k] > limits[k] for k in limits), (got, limits)


def test_the_serving_control_fails_its_limit():
    cell = small("serve.granite8b.steady")
    reqs = cell.inputs(SEED, 0.5)
    init, key = serve.stack_init(cell, SEED)
    stack = init(key)
    picked = [dict(r, output=r["prompt"][: r["budget"]]) for r in reqs[:8]]
    gaps = serve.served_gaps(cell, lambda d: jax.tree.map(lambda x: x[d], stack), picked,
                             low=True)
    assert gaps.max() > cell.limits["limits"]["served_logit_gap"]
