"""Federated-LM path: batched local SGD == the per-client loop.

The batched stage (``core.local_update.build_local_update``) replaces the
per-client Python dispatch loop everywhere — these tests pin the refactor:

* the vmapped stage reproduces the sequential per-client reference at
  fp32, standalone and through masked participation bitwise, and through
  the round engine and the sync scheduler within fp32 rounding;
* the fused-SGD kernel path (Pallas backend) is dense-equivalent;
* bf16 client models track the fp32 trajectory within mixed-precision
  tolerance;
* the ``federated-lm-ring`` scenario and the ``FederatedLM`` dataset
  behave as advertised.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import FLSpec, init_stacked
from repro.core.backends import resolve_backend
from repro.core.local_update import (
    build_local_update, build_sequential_local_update, fused_sgd_applicable,
)
from repro.core.round_engine import build_fl_round_step
from repro.data import FederatedLM
from repro.models import CausalLM
from repro.models.config import ArchConfig

C, D, SEQ, B = 8, 4, 16, 2
LR = 0.1


def _arch(precision="float32"):
    return ArchConfig(
        name=f"test-lm-{precision}", family="dense",
        num_layers=2, d_model=32, d_ff=64, vocab_size=128,
        num_heads=2, num_kv_heads=1, head_dim=16,
        dtype=precision, remat=False, attn_chunk=SEQ, tie_embeddings=True,
    )


def _fl(**kw):
    base = dict(num_clients=C, num_clusters=D, tau1=2, tau2=1, alpha=1,
                learning_rate=LR, topology="ring")
    base.update(kw)
    return FLSpec(**base)


def _window(iters, seed=0):
    ds = FederatedLM.generate(C, 64, SEQ, 128, seed=seed)
    rng = np.random.default_rng(seed)
    draws = [ds.stacked_batch(B, rng) for _ in range(iters)]
    return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *draws)


def _bitwise(tree_a, tree_b):
    for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# The compiled round/sync programs and the per-client loop are different XLA
# programs: fusion and reduction order differ, so fp32 results agree to
# rounding, not bit for bit.  fp32 unit roundoff is 6e-8; a few SGD steps
# and transitions of reordered sums stay well under 1e-6 on these O(1)
# weights, while a wrong transition or update moves them by >= lr * grad,
# orders of magnitude more.
FP32_REORDER_ATOL = 1e-6


def _close(tree_a, tree_b, atol=FP32_REORDER_ATOL):
    for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=atol, rtol=0)


def test_batched_stage_bitwise_equals_sequential():
    """vmapped value_and_grad + update == C separate jitted dispatches."""
    model = CausalLM(_arch())
    opt = optim.sgd(LR)
    batched = jax.jit(build_local_update(model, opt))
    sequential = build_sequential_local_update(model, opt)
    window = _window(3)

    p1 = init_stacked(model, C, jax.random.PRNGKey(0))
    p2 = jax.tree.map(lambda x: x.copy(), p1)
    s1 = s2 = ()
    for i in range(3):
        batch = jax.tree.map(lambda x: x[i], window)
        p1, s1, l1 = batched(p1, s1, batch)
        p2, s2, l2 = sequential(p2, s2, batch)
    _bitwise(p1, p2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_round_engine_bitwise_vs_python_loop():
    """R=2 superstep == the naive loop (sequential stage + dense transitions),
    within fp32 reordering (``FP32_REORDER_ATOL``)."""
    model = CausalLM(_arch())
    opt = optim.sgd(LR)
    fl = _fl()
    proto = fl.protocol()
    backend = resolve_backend("dense", proto.clusters, proto.P(), fl.alpha)
    ipr = fl.tau1 * fl.tau2
    rps = 2
    window = _window(rps * ipr)

    step_fn = jax.jit(build_fl_round_step(model, opt, fl, backend=backend,
                                          rounds_per_step=rps))
    p1 = init_stacked(model, C, jax.random.PRNGKey(1))
    p2 = jax.tree.map(lambda x: x.copy(), p1)
    p1, _, _ = step_fn(p1, (), window)

    sequential = build_sequential_local_update(model, opt)
    s2, k = (), 0
    for _ in range(rps):
        for _ in range(fl.tau2):
            for _ in range(fl.tau1):
                batch = jax.tree.map(lambda x: x[k], window)
                p2, s2, _ = sequential(p2, s2, batch)
                k += 1
            p2 = backend.transition(p2, "intra")
        p2 = backend.transition(p2, "inter")
    _close(p1, p2)


def test_sync_scheduler_bitwise_vs_sequential_reference():
    """SyncScheduler iterations == sequential updates + scheduled transitions,
    within fp32 reordering (``FP32_REORDER_ATOL``)."""
    from repro.core.runtime import SyncScheduler

    model = CausalLM(_arch())
    fl = _fl()
    proto = fl.protocol()
    backend = resolve_backend("dense", proto.clusters, proto.P(), fl.alpha)
    ipr = fl.tau1 * fl.tau2
    window = _window(ipr)

    sched = SyncScheduler(proto, backend="dense")
    sched.bind(model, seed=0)
    p_ref = init_stacked(model, C, 0)
    _bitwise(sched.params, p_ref)  # same seed -> same init

    sequential = build_sequential_local_update(model, optim.sgd(fl.learning_rate))
    s_ref = ()
    for k in range(1, ipr + 1):
        batch = jax.tree.map(lambda x: x[k - 1], window)
        sched.advance(k, batch)
        p_ref, s_ref, _ = sequential(p_ref, s_ref, batch)
        event = proto.event_at(k)
        if event != "local":
            p_ref = backend.transition(p_ref, event)
    _close(sched.params, p_ref)


def test_masked_participation_bitwise():
    """Round step with traced weights == loop with transition(weights=w)."""
    from repro.participation import renormalize_weights

    model = CausalLM(_arch())
    opt = optim.sgd(LR)
    fl = _fl()
    proto = fl.protocol()
    backend = resolve_backend("dense", proto.clusters, proto.P(), fl.alpha)
    ipr = fl.tau1 * fl.tau2
    window = _window(ipr)

    mask = np.array([1, 0, 1, 1, 0, 1, 1, 0], dtype=bool)
    w = jnp.asarray(
        renormalize_weights(proto.clusters.m_hat(), proto.clusters.assignments,
                            mask),
        jnp.float32,
    )

    step_fn = jax.jit(build_fl_round_step(model, opt, fl, backend=backend,
                                          participation=True))
    p1 = init_stacked(model, C, jax.random.PRNGKey(2))
    p2 = jax.tree.map(lambda x: x.copy(), p1)
    p1, _, _ = step_fn(p1, (), window, w[None])

    sequential = build_sequential_local_update(model, opt)
    s2, k = (), 0
    for _ in range(fl.tau2):
        for _ in range(fl.tau1):
            batch = jax.tree.map(lambda x: x[k], window)
            p2, s2, _ = sequential(p2, s2, batch)
            k += 1
        p2 = backend.transition(p2, "intra", weights=w)
    p2 = backend.transition(p2, "inter", weights=w)
    _bitwise(p1, p2)


def test_bf16_round_tracks_fp32():
    """bf16 client models follow the fp32 loss trajectory within tolerance."""
    window = _window(4)
    losses = {}
    for precision in ("float32", "bfloat16"):
        model = CausalLM(_arch(precision))
        fl = _fl(tau2=2)
        step_fn = jax.jit(build_fl_round_step(model, optim.sgd(LR), fl))
        params = init_stacked(model, C, jax.random.PRNGKey(3))
        _, _, ls = step_fn(params, (), window)
        losses[precision] = np.asarray(ls, np.float64)
        assert np.all(np.isfinite(losses[precision]))
    np.testing.assert_allclose(losses["bfloat16"], losses["float32"],
                               atol=0.15)


def test_fused_sgd_stage_matches_dense_fp32():
    """Pallas fused-SGD path (the kernel on every leaf) == dense stage."""
    model = CausalLM(_arch())
    opt = optim.sgd(LR)
    fl = _fl()
    proto = fl.protocol()
    dense = resolve_backend("dense", proto.clusters, proto.P(), fl.alpha)
    pallas = resolve_backend("pallas", proto.clusters, proto.P(), fl.alpha,
                             interpret=True)
    assert not fused_sgd_applicable(opt, dense)
    assert fused_sgd_applicable(opt, pallas)

    # leaves of every rank, from the (C, 32) norm scales up, stream in their
    # own layout: no flattening, no dense fallback
    params = init_stacked(model, C, jax.random.PRNGKey(4))
    assert {leaf.ndim for leaf in jax.tree.leaves(params)} >= {2, 4}

    batch = jax.tree.map(lambda x: x[0], _window(1))
    p_dense, _, l_dense = jax.jit(build_local_update(model, opt, backend=dense))(
        params, (), batch
    )
    p_fused, _, l_fused = jax.jit(
        build_local_update(model, opt, backend=pallas, tile_m=512)
    )(params, (), batch)
    np.testing.assert_array_equal(np.asarray(l_dense), np.asarray(l_fused))
    for a, b in zip(jax.tree.leaves(p_dense), jax.tree.leaves(p_fused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_federated_lm_scenario_smoke():
    """federated-lm-ring is registered, builds, steps, and evaluates."""
    from repro.scenarios import build_scenario, get_scenario

    sc = get_scenario("federated-lm-ring")
    assert sc.scheduler == "round" and sc.dataset == "lm"

    run = build_scenario(
        "federated-lm-ring",
        num_samples=64, seq_len=SEQ, vocab_size=128, batch_size=B,
        arch_overrides=dict(num_layers=2, d_model=32, d_ff=64, num_heads=2,
                            num_kv_heads=1, head_dim=16, attn_chunk=SEQ),
    )
    ev = run.runtime.step(run.batch_source())
    assert np.all(np.isfinite(np.asarray(ev.losses, np.float64)))
    loss, _ = run.runtime.evaluate(run.eval_batch)
    assert np.isfinite(loss)


def test_federated_lm_dataset():
    """Stacked non-IID corpora: shapes, dtypes, distinct per-client streams."""
    ds = FederatedLM.generate(C, 32, SEQ, 128, seed=7)
    assert ds.tokens.shape == (C, 32, SEQ + 1)
    assert ds.num_clients == C
    assert list(ds.data_sizes()) == [32] * C
    # non-IID: per-client Markov chains are seeded differently
    assert not np.array_equal(ds.tokens[0], ds.tokens[1])

    rng = np.random.default_rng(0)
    batch = ds.stacked_batch(B, rng)
    assert batch["tokens"].shape == (C, B, SEQ)
    assert batch["labels"].shape == (C, B, SEQ)
    np.testing.assert_array_equal(
        np.asarray(batch["tokens"][:, :, 1:]), np.asarray(batch["labels"][:, :, :-1])
    )
    ev = ds.eval_batch(8, seed=0)
    assert ev["tokens"].shape == (8, SEQ)
