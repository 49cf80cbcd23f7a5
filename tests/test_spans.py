"""The round's named layers: device scopes in the compiled round step's HLO
``op_name`` metadata, and host spans in a profiler trace of
``RoundScheduler.step``.

Scopes are metadata only, so a profiler trace of the chip names every op by
the layer that issued it (``repro.spans``); these tests pin that the
names survive vmap, scan, autodiff and rematerialisation, that the
per-event steps of the sync scheduler name their transitions too, and that
every path of the round scheduler's step records its two host spans.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim, spans
from repro.core import FLSpec, init_stacked, make_run
from repro.core.backends import resolve_backend
from repro.core.config import ExecSpec, FleetSpec, ModelSpec, RunConfig
from repro.core.round_engine import build_fl_round_step
from repro.data import FederatedLM
from repro.models import CausalLM
from repro.models.config import ArchConfig

C, D, SEQ = 4, 2, 16


def _arch():
    # remat on, as the chip configurations train: backward ops then sit
    # under checkpoint/rematted_computation paths
    return ArchConfig(name="spans-lm", family="dense", num_layers=1, d_model=32, d_ff=64,
                      vocab_size=128, num_heads=2, num_kv_heads=1, head_dim=16,
                      dtype="bfloat16", remat=True, attn_chunk=8)


def _op_names(backend_name: str) -> list:
    model = CausalLM(_arch())
    fl = FLSpec(num_clients=C, num_clusters=D, tau1=2, tau2=1, alpha=1,
                learning_rate=0.1, topology="ring")
    proto = fl.protocol()
    backend = resolve_backend(backend_name, proto.clusters, proto.P(), fl.alpha)
    step = jax.jit(build_fl_round_step(model, optim.sgd(0.1), fl, backend=backend))
    params = jax.eval_shape(lambda k: init_stacked(model, C, k), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, C, 1, SEQ), jnp.int32)
    text = step.lower(params, (), {"tokens": tokens, "labels": tokens}).compile().as_text()
    # full paths only: a reduction's body carries a path relative to its caller
    return [n for n in re.findall(r'op_name="([^"]*)"', text) if n.startswith("jit(")]


@pytest.fixture(scope="module", params=["dense", "pallas"])
def op_names(request):
    """The round step's op paths, dense and fused Pallas (interpret mode off
    a TPU)."""
    return request.param, _op_names(request.param)


def test_every_scope_names_ops_of_the_round_step(op_names):
    _, names = op_names
    for scope in spans.SCOPES:
        assert any(scope in n for n in names), scope


def test_scopes_nest_as_the_round_does(op_names):
    _, names = op_names
    under = {s: [n for n in names if s in n] for s in spans.SCOPES}
    for inner in (spans.FORWARD_BACKWARD, spans.OPTIMIZER):
        assert all(spans.LOCAL_UPDATE in n for n in under[inner]), inner
    for layer in (spans.EMBED, spans.MLP, spans.LM_HEAD):
        assert all(spans.FORWARD_BACKWARD in n for n in under[layer]), layer
    for stage in (spans.TRANSITION_INTRA, spans.TRANSITION_INTER):
        assert not any(spans.LOCAL_UPDATE in n for n in under[stage]), stage


def test_optimizer_update_sits_under_its_scope(op_names):
    backend, names = op_names
    under = [n for n in names if spans.OPTIMIZER in n]
    if backend == "pallas":  # the fused SGD kernel's call
        assert any("sgd_update" in n for n in under)
        assert all(spans.OPTIMIZER in n for n in names if "sgd_update" in n)
    else:  # the vmapped SGD update: w - lr * g
        assert any(n.endswith("/sub") for n in under)


def test_backward_ops_of_attention_keep_its_scope(op_names):
    _, names = op_names
    backward = [n for n in names if spans.ATTENTION in n and "transpose(" in n]
    assert backward
    # and rematerialised forward ops too
    assert any(spans.ATTENTION in n and "rematted_computation" in n for n in names)


def _runtime(scheduler: str, fleet: dict):
    arch = _arch()
    runtime = make_run(RunConfig(
        model=ModelSpec(instance=CausalLM(arch)),
        fleet=FleetSpec(**fleet),
        exec=ExecSpec(scheduler=scheduler, backend="dense", topology="ring", tau1=2, tau2=1,
                      alpha=1, learning_rate=0.1),
        num_clients=C, num_clusters=D, seed=0,
    ))
    ds = FederatedLM.generate(C, 32, SEQ, arch.vocab_size, seed=0)
    return runtime, lambda i: ds.stacked_batch(1, np.random.default_rng(i))


@pytest.mark.parametrize("event", ["intra", "inter"])
def test_sync_scheduler_steps_scope_their_transition(event):
    """The sync scheduler's per-event step: its local SGD under
    ``sdfeel.local_update`` and its transition under the event's scope."""
    runtime, source = _runtime("sync", {})
    sched = runtime.scheduler
    text = sched._step_fns[event].lower(sched.params, source(1)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    scope = {"intra": spans.TRANSITION_INTRA, "inter": spans.TRANSITION_INTER}[event]
    for want in (spans.LOCAL_UPDATE, spans.FORWARD_BACKWARD, spans.OPTIMIZER, scope):
        assert any(want in n for n in names), want


# -- host spans ---------------------------------------------------------------

def _host_span_counts(profile_dir) -> dict:
    path, = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    counts = dict.fromkeys(spans.SPANS, 0)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in counts:
                    counts[e.name] += 1
    return counts


PATHS = {
    "resident": {},
    "sampling": {"participation": {"strategy": "uniform-k", "k": 1, "seed": 0}},
    "fault": {"faults": [{"kind": "client-crash", "round": 0, "client": 1}]},
    "offload": {"participation": {"strategy": "uniform-k", "k": 1, "seed": 0},
                "store": {"kind": "host-offload", "k_max": 2}},
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_round_scheduler_step_records_its_host_spans(path, tmp_path):
    runtime, source = _runtime("round", PATHS[path])
    runtime.step(source).losses.block_until_ready()  # compiles outside the trace
    steps = 2
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(steps):
            runtime.step(source).losses.block_until_ready()
    assert _host_span_counts(tmp_path) == {spans.STAGE: steps, spans.DISPATCH: steps}
