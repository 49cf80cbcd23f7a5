"""CPU tests of the attention kernel's reader and of where the kernel sits
in the training cell's program.

The training cell's round step, compiled for a described v5e as the one
chip of the cell runs it, calls the Pallas flash-attention kernel's
forward, dq and dkv passes under the ``sdfeel.attention`` scope, with no
XLA ``while`` left there; ``attention_kernel_ms.train`` names those ops.
Compiled for a described ``v5e:2x2`` with the clients on the mesh (the
collective backend), the model keeps the XLA path, which the compiler can
partition.  The reader reads ms per round from a trace holding the kernel's
ops, and nothing from the window recorded on the chip before the kernel.

    JAX_PLATFORMS=cpu python3 -m pytest -q tests/bench_harness/test_attention_kernel.py
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import trace  # noqa: E402
from bench.common import Cell, load_module  # noqa: E402
from repro import spans  # noqa: E402

CELL = "train.granite8b.seq1024"
READER = load_module(ROOT / "bench" / "metrics" / "attention_kernel_ms.train.py")
TESTDATA = ROOT / "bench" / "testdata"
MS = 1_000_000


def instructions(text: str) -> dict:
    """``{instruction name: (its HLO text, its op_name path)}``.  An
    instruction's text may run over several lines (a kernel's metadata
    attribute holds line breaks), up to the next instruction."""
    starts = list(re.finditer(r"^\s*(?:ROOT )?%(\S+) = ", text, re.MULTILINE))
    out = {}
    for m, nxt in zip(starts, starts[1:] + [None]):
        body = text[m.start(): nxt.start() if nxt else len(text)]
        path = re.search(r'metadata=\{op_name="([^"]*)"', body)
        out[m.group(1)] = (body, path.group(1) if path else "")
    return out


def compile_round_step(monkeypatch, devices: int, sharding, backend_for):
    """The cell's round step (its configuration and federation at full
    width), lowered and compiled for described TPU devices as on a host of
    ``devices`` chips; its instructions."""
    import jax
    import jax.numpy as jnp
    from repro import optim
    from repro.core import FLSpec, init_stacked

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    cell = Cell(CELL)
    model = cell.program_model.build(cell.config)
    train = cell.config["training"]
    fed, params = cell.traffic["federation"], cell.traffic["params"]
    clients = fed["clients"]
    clusters = clients if devices > 1 else fed["clusters"]  # the collective ring needs D >= 3
    fl = FLSpec(num_clients=clients, num_clusters=clusters, tau1=train["tau1"],
                tau2=train["tau2"], alpha=train["alpha"],
                learning_rate=train["learning_rate"], topology=train["topology"])
    proto = fl.protocol()
    from repro.core.round_engine import build_fl_round_step

    backend = backend_for(proto, fl.alpha)
    step = jax.jit(build_fl_round_step(model, optim.sgd(fl.learning_rate), fl,
                                       backend=backend))
    state = jax.eval_shape(lambda k: init_stacked(model, clients, k), jax.random.PRNGKey(0))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
                         state)
    iters = train["tau1"] * train["tau2"]
    tokens = jax.ShapeDtypeStruct((iters, clients, params["batch"], params["seq_len"]),
                                  jnp.int32, sharding=sharding)
    text = step.lower(state, (), {"tokens": tokens, "labels": tokens}).compile().as_text()
    return instructions(text)


def attention_whiles(ops: dict) -> list:
    return [n for n, (_, path) in ops.items()
            if n.startswith("while") and spans.ATTENTION in path]


def test_the_cell_runs_the_kernel_under_the_attention_scope(described_chip, monkeypatch):
    from repro.core.backends import PallasBackend

    ops = compile_round_step(
        monkeypatch, 1, described_chip,
        lambda proto, alpha: PallasBackend(proto.clusters, np.asarray(proto.P()), alpha,
                                           interpret=False))
    kernel = {n: path for n, (body, path) in ops.items()
              if "tpu_custom_call" in body and any(w in n for w in READER.KERNEL)}
    for word in READER.KERNEL:  # forward, dq, dkv: each names an op of the program
        assert any(word in n for n in kernel), (word, sorted(kernel))
    assert all(spans.ATTENTION in path for path in kernel.values()), kernel
    # two forwards (the first and the rematerialised one) and one backward
    assert sum("splash_mqa_fwd" in n for n in kernel) == 2
    assert attention_whiles(ops) == []
    # no other op's own name holds the reader's words (outputs are read by
    # get-tuple-element, which is no device op)
    others = [n for n, (body, _) in ops.items() if n not in kernel
              and any(w in n for w in READER.KERNEL)]
    assert others == []


def test_clients_on_a_mesh_keep_the_xla_path(described_chip, monkeypatch):
    """Four described chips, one client each, the collective backend: the
    compiler partitions the model step, so attention stays on the XLA path
    (a Mosaic kernel cannot be partitioned) and the step compiles."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.core.backends import CollectiveBackend

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("data",))
    ops = compile_round_step(
        monkeypatch, len(topo.devices), NamedSharding(mesh, PartitionSpec()),
        lambda proto, alpha: CollectiveBackend(proto.clusters, np.asarray(proto.P()), alpha,
                                               mesh=mesh))
    assert not any(w in n for n in ops for w in READER.KERNEL)
    assert attention_whiles(ops)
    assert jax.default_backend() == "tpu"  # the selection saw a TPU host of four chips


def kernel_trace(rounds: int = 2) -> dict:
    """A compact trace of ``rounds`` rounds 100 ms apart: per round the
    kernel's forward twice (3 ms each), dq (2 ms) and dkv (4 ms), and a
    matmul fusion that reads the forward's output."""
    host = [["bench.window", 0, rounds * 100 * MS]]
    ops = []
    for r in range(rounds):
        t = r * 100 * MS
        ops += [["%splash_mqa_fwd_residuals.16 = (f32[4,8,512,128]) custom-call(%q)", t, 3 * MS],
                ["%fusion.7 = bf16[4,1024,4096] fusion(%jit_flash_attention_.44)", t + 3 * MS,
                 5 * MS],
                ["%splash_mqa_fwd_residuals.17 = (f32[4,8,512,128]) custom-call(%q)",
                 t + 10 * MS, 3 * MS],
                ["%splash_mqa_dkv_no_residuals.8 = (f32[4,8,512,128]) custom-call(%q)",
                 t + 20 * MS, 4 * MS],
                ["%splash_mqa_dq_no_residuals.8 = (f32[4,8,512,128]) custom-call(%q)",
                 t + 30 * MS, 2 * MS]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
    ]}


def test_reader_reads_kernel_ms_per_round():
    tr = kernel_trace()
    assert READER.read({"reduction": trace.reduce(tr), "rounds": 2}) == pytest.approx(12.0)
    tr["planes"][1]["lines"][0]["events"] = [
        e for e in tr["planes"][1]["lines"][0]["events"] if "splash" not in e[0]]
    assert READER.read({"reduction": trace.reduce(tr), "rounds": 2}) is None


@pytest.mark.parametrize("path", sorted(TESTDATA.glob("*.layers.gz")), ids=lambda p: p.name)
def test_reader_finds_nothing_in_a_window_recorded_before_the_kernel(path):
    tr = trace.load(str(path))
    assert READER.read({"reduction": trace.reduce(tr), "rounds": tr["rounds"]}) is None
