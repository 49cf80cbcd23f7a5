"""CPU tests of the per-layer breakdown over the program's named scopes
(``bench/layers.py``): the op paths read from compiled HLO, the union that
counts a ``while`` op and its body once, host spans that name idle gaps,
the earlier readers unchanged by spans and scopes in the trace, the scope
words in the program compiled for a described v5e and in the round step
lowered from a scheduler's own state, and a window recorded on the chip.

    JAX_PLATFORMS=cpu python3 -m pytest -q tests/bench_harness/test_layers.py
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import layers, trace  # noqa: E402
from bench.common import load_module  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402
from repro import spans  # noqa: E402

MS = 1_000_000
TESTDATA = ROOT / "bench" / "testdata"
EARLIER = ("mfu.train", "fused_sgd_roofline", "fused_transition_roofline", "idle_share.train")
READERS = {name: load_module(ROOT / "bench" / "metrics" / f"{name}.py") for name in EARLIER}
OUTERMOST = (spans.LOCAL_UPDATE, spans.TRANSITION_INTRA, spans.TRANSITION_INTER)  # in no other


def op(name: str) -> str:
    """A TPU ``XLA Ops`` event name: the instruction's HLO text, no op_name."""
    return f"%{name} = bf16[4,1024]{{1,0:T(8,128)(2,1)}} fusion(bf16[4,1024] %p), kind=kLoop"


LOCAL = "jit(round_step)/while/body/closed_call/sdfeel.local_update/while/body/closed_call/"
FB = LOCAL + "sdfeel.forward_backward/vmap(jvp())/"
# one 60 ms round: (instruction, op_name path, start ms, duration ms)
ROUND = [
    ("while.636", FB + "while", 0, 30),  # the layer scan, wrapping its body
    ("fusion.1", FB + "while/body/sdfeel.attention/dot_general", 2, 5),
    ("fusion.2", FB.replace("jvp()", "transpose(jvp())") + "while/body/checkpoint/"
     "rematted_computation/sdfeel.attention/dot_general", 8, 6),
    ("fusion.3", FB + "while/body/sdfeel.mlp/dot_general", 15, 10),
    ("fusion.4", FB + "sdfeel.lm_head/dot_general", 30, 8),
    ("fusion.5", LOCAL + "sdfeel.attention/iota", 38, 2),  # CSE kept the layer's name only
    ("sgd_update.153", LOCAL + "sdfeel.optimizer/jit(sgd_update)/pallas_call", 40, 6),
    ("fused_transition.7", "jit(round_step)/sdfeel.transition.intra/pallas_call", 46, 5),
    ("fused_transition.8", "jit(round_step)/sdfeel.transition.inter/pallas_call", 51, 5),
    ("copy.9", "jit(round_step)/copy", 57, 1),
]


def scoped_trace(rounds: int = 2, program: bool = True) -> dict:
    """A compact trace of ``rounds`` rounds 60 ms apart.  With ``program``:
    the program's host spans beside the benchmark's, and the scopes map;
    without: the trace of a program that names nothing."""
    host = [["bench.window", 0, rounds * 60 * MS]]
    ops = []
    for r in range(rounds):
        t = r * 60 * MS
        host += [["bench.dispatch", t, 2 * MS], ["bench.wait", t + 2 * MS, 58 * MS]]
        ops += [[op(name), t + s * MS, d * MS] for name, _, s, d in ROUND]
    tr = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_round_step", 0, rounds * 60 * MS]]},
            {"name": "XLA Ops", "events": ops}]},
    ]}
    if program:
        program_spans = []
        for r in range(rounds):
            t = r * 60 * MS
            program_spans += [[spans.STAGE, t, int(1.5 * MS)],
                              [spans.DISPATCH, t + int(1.5 * MS), MS // 2]]
        tr["planes"][0]["lines"].append({"name": "program spans", "events": program_spans})
        paths = {name: path for name, path, _, _ in ROUND}
        tr["scopes"] = layers.op_scopes(ops, paths)
    return tr


def test_op_paths_come_from_the_compiled_program():
    text = "\n".join([
        'ENTRY %main {',
        '  %fusion.1 = bf16[4]{0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(f)/sdfeel.mlp/dot"}',
        '  ROOT %while.2 = (s32[]) while(%t), body=%b, metadata={op_name="jit(f)/while" '
        'source_file="x.py" source_line=3}',
        '  %p = bf16[4]{0} parameter(0)',
        '}'])
    paths = layers.scopes_from_hlo(text)
    assert paths == {"fusion.1": "jit(f)/sdfeel.mlp/dot", "while.2": "jit(f)/while"}
    assert layers.instruction("%while.2 = (s32[]{:T(128)}, bf16[4]) while(...)") == "while.2"
    assert layers.instruction("fusion.1") == "fusion.1"
    events = [["%fusion.1 = bf16[4] fusion()", 0, 1], ["%copy.3 = bf16[4] copy()", 1, 1]]
    assert layers.op_scopes(events, paths) == {events[0][0]: "jit(f)/sdfeel.mlp/dot"}


def test_a_while_and_the_body_it_wraps_count_once():
    tr = scoped_trace()
    red = trace.reduce(tr)
    events, scopes = red["per_chip"]["/device:TPU:0"], tr["scopes"]
    t0, t1 = red["t0"], red["t1"]
    # forward_backward: the 30 ms while (21 ms of its body inside it) and the 8 ms head
    assert layers.scoped_ns(events, scopes, t0, t1, ("sdfeel.forward_backward",)) == 2 * 38 * MS
    # attention: forward, rematerialised forward and backward, and the op CSE kept
    assert layers.scoped_ns(events, scopes, t0, t1, ("sdfeel.attention",)) == 2 * 13 * MS
    # the window clips: the first half of the first round
    assert layers.scoped_ns(events, scopes, 0, 30 * MS, ("sdfeel.forward_backward",)) == 30 * MS
    # the op names alone carry no path
    assert layers.scoped_ns(events, {}, t0, t1, ("sdfeel.",)) == 0


def test_an_unscoped_while_counts_only_outside_its_scoped_body():
    ops = [["%while.1 = ()", 0, 10 * MS], ["%fusion.2 = ()", 1 * MS, 4 * MS],
           ["%fusion.3 = ()", 3 * MS, 4 * MS], ["%copy.4 = ()", 12 * MS, 1 * MS]]
    scopes = {ops[1][0]: "jit(f)/while/body/sdfeel.mlp/dot",
              ops[2][0]: "jit(f)/while/body/sdfeel.attention/dot"}
    assert layers.unscoped_by_op(ops, scopes, 0, 20 * MS) == {ops[0][0]: 4 * MS,
                                                              ops[3][0]: 1 * MS}


def test_breakdown_adds_up_to_the_busy_time():
    out = layers.breakdown(scoped_trace(), rounds=2)
    ms = out["device_ms"]
    assert ms["busy"] == pytest.approx(57.0)  # idle from 56 to 57 ms of each round
    assert ms["unscoped"] == pytest.approx(1.0)
    assert ms[spans.LOCAL_UPDATE] == pytest.approx(46.0)
    assert sum(ms[k] for k in OUTERMOST) + ms["unscoped"] == pytest.approx(ms["busy"])
    assert (ms[spans.ATTENTION], ms[spans.MLP], ms[spans.LM_HEAD],
            ms[spans.OPTIMIZER]) == pytest.approx((13.0, 10.0, 8.0, 6.0))
    assert ms[spans.TRANSITION_INTRA] == ms[spans.TRANSITION_INTER] == pytest.approx(5.0)
    assert out["host_ms"] == pytest.approx({spans.STAGE: 1.5, spans.DISPATCH: 0.5})
    assert out["largest_unscoped_s"] == [[op("copy.9")[:160], pytest.approx(2e-3)]]


def test_a_gap_inside_a_program_span_is_named_by_it():
    """The second round's ops start 3 ms late: the chip idles from 58 to
    63 ms, around the host's stage span (60 to 61.5 ms) inside the
    benchmark's dispatch span (60 to 62 ms).  The innermost span names it."""
    def late(tr):
        for e in tr["planes"][1]["lines"][1]["events"]:
            e[1] += 3 * MS if e[1] >= 60 * MS else 0
        return trace.reduce(tr)["idle_gaps"][0]

    assert late(scoped_trace()) == [spans.STAGE, pytest.approx(5e-3)]
    assert late(scoped_trace(program=False)) == ["bench.dispatch", pytest.approx(5e-3)]


def ctx_for(tr: dict, rounds: int = 2) -> dict:
    return {"reduction": trace.reduce(tr), "rounds": rounds, "chips": 1,
            "peaks": peaks_for("TPU v5 lite"),
            "costs": {"model_flops": 2.0e12, "sgd": (1.0e6, 3.0e9), "transition": (1.0e7, 2.0e9)}}


@pytest.mark.parametrize("name", EARLIER)
def test_earlier_readers_read_the_same_with_program_spans_and_scopes(name):
    a = READERS[name].read(ctx_for(scoped_trace()))
    b = READERS[name].read(ctx_for(scoped_trace(program=False)))
    assert a is not None and a == b


def test_layer_names_are_the_programs_scope_names():
    """The breakdown names each layer by the program's scope or span, under
    its one prefix."""
    out = layers.breakdown(scoped_trace(), rounds=2)
    assert set(out["device_ms"]) == set(spans.SCOPES) | {"unscoped", "busy"}
    assert set(out["host_ms"]) == set(spans.SPANS)
    assert all(s.startswith(spans.PREFIX) for s in spans.SCOPES + spans.SPANS)
    assert set(OUTERMOST) <= set(spans.SCOPES)
    # no name holds a word an earlier reader matches op names by
    words = READERS["fused_sgd_roofline"].KERNEL + READERS["fused_transition_roofline"].KERNEL
    assert not any(w in s for w in words for s in spans.SCOPES + spans.SPANS)


def test_every_layer_names_ops_of_the_round_step_compiled_for_the_chip(described_chip):
    """The fused Pallas round step, compiled for a v5e: each layer's scope
    names ops, and the fused SGD kernel's call sits under the optimizer."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import optim
    from repro.core import FLSpec, init_stacked
    from repro.core.backends import PallasBackend
    from repro.core.round_engine import build_fl_round_step
    from repro.models import CausalLM
    from repro.models.config import ArchConfig

    arch = ArchConfig(name="layers-lm", family="dense", num_layers=1, d_model=256, d_ff=512,
                      vocab_size=1024, num_heads=2, num_kv_heads=1, head_dim=128,
                      dtype="bfloat16", remat=True, attn_chunk=128)
    model = CausalLM(arch)
    fl = FLSpec(num_clients=4, num_clusters=2, tau1=2, tau2=1, alpha=1, learning_rate=0.01,
                topology="ring")
    proto = fl.protocol()
    backend = PallasBackend(proto.clusters, np.asarray(proto.P()), fl.alpha, interpret=False)
    step = jax.jit(build_fl_round_step(model, optim.sgd(0.01), fl, backend=backend))
    shard = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=described_chip)  # noqa: E731
    params = jax.tree.map(shard, jax.eval_shape(lambda k: init_stacked(model, 4, k),
                                                jax.random.PRNGKey(0)))
    tokens = shard(jax.ShapeDtypeStruct((2, 4, 1, 256), jnp.int32))
    text = step.lower(params, (), {"tokens": tokens, "labels": tokens}).compile().as_text()
    paths = layers.scopes_from_hlo(text)
    for scope in spans.SCOPES:
        assert any(scope in p for p in paths.values()), scope
    calls = re.findall(r"%(\S+) = .* custom-call\(.*tpu_custom_call", text)
    sgd = [c for c in calls if "sgd_update" in c]
    assert sgd and all(spans.OPTIMIZER in paths[c] for c in sgd)
    transitions = [c for c in calls if "fused_transition" in c]
    assert transitions and all("sdfeel.transition." in paths[c] for c in transitions)


def test_the_round_step_lowers_from_what_the_scheduler_holds():
    """``round_step_scopes`` lowers the training cell's round step, at test
    size, from the scheduler's state and a batch staged as its step stages
    one: every scope names ops of it."""
    from bench import train
    from bench.common import Cell, benchmark

    cell = Cell("train.granite8b.seq1024", benchmark())
    cell.config.update(d_model=64, d_ff=128, vocab_size=512, num_heads=4, num_kv_heads=2,
                       head_dim=16)
    cell.traffic["federation"]["backend"] = "dense"
    cell.traffic["params"].update(seq_len=32, pool=16)
    runtime, source, _, _ = train.build(cell, 2**31 + 11)
    runtime.step(source).losses.block_until_ready()
    paths = layers.round_step_scopes(runtime.scheduler, source)
    for scope in spans.SCOPES:
        assert any(scope in p for p in paths.values()), scope


def recorded_windows():
    return sorted(TESTDATA.glob("*.layers.gz"))


@pytest.mark.parametrize("path", recorded_windows(), ids=lambda p: p.name)
def test_a_window_recorded_on_the_chip_adds_up(path, capsys):
    """A window kept with ``bench/layers.py --keep``: every layer holds
    device time, and the outermost scopes with the unscoped rest add up to
    the busy time within 2%."""
    assert path.stat().st_size < 1_000_000
    tr = trace.load(str(path))
    out = layers.breakdown(tr, tr["rounds"])
    ms = out["device_ms"]
    assert all(ms[scope] > 0 for scope in spans.SCOPES), ms
    assert sum(ms[k] for k in OUTERMOST) + ms["unscoped"] == pytest.approx(
        ms["busy"], rel=0.02)
    assert (ms[spans.ATTENTION] < ms[spans.FORWARD_BACKWARD] < ms[spans.LOCAL_UPDATE]
            < ms["busy"])
    assert all(v > 0 for v in out["host_ms"].values())
    layers.main(["--kept", str(path)])
    assert json.loads(capsys.readouterr().out)["device_ms"] == pytest.approx(ms)
