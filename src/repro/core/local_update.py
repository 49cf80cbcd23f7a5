"""Batched local-update stage: one compiled dispatch for all clients.

The SD-FEEL local-update phase runs ``tau1`` SGD micro-steps on every
participating client between aggregations.  The naive driver loops over
clients in Python — ``C`` separate ``jit`` dispatches per micro-step, each
touching one client's parameter tree.  This module builds the batched
alternative used by every scheduler: the client trees are *stacked* along a
leading ``(C, ...)`` axis and one ``vmap`` over ``jax.value_and_grad`` plus a
vmapped optimizer update turns the whole fleet's micro-step into a single
XLA program.  On a device mesh the stacked axis is the ``clients`` /
``data`` mesh axis, so the same program shards across devices with no code
change (see ``core.backends.CollectiveBackend``).

``build_local_update`` is the shared stage consumed by
``build_fl_round_step``, ``build_fl_train_step`` and ``SyncScheduler``;
``build_sequential_local_update`` is the per-client Python-loop reference it
is benchmarked (benchmarks/lm_throughput.py) and bitwise-tested
(tests/test_federated_lm.py) against.

Fused-kernel path: when the optimizer is plain SGD with a static learning
rate and the selected aggregation backend is Pallas, the parameter update
runs through ``kernels.fused_sgd`` (one fused multiply-subtract per tile,
f32 accumulation), every leaf in its own layout, so the fused path is
dense-equivalent leaf by leaf.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from .. import spans

PyTree = Any

__all__ = [
    "build_local_update",
    "build_sequential_local_update",
    "fused_sgd_applicable",
]


def fused_sgd_applicable(opt, backend) -> bool:
    """True when the (optimizer, backend) pair routes through fused_sgd.

    The kernel implements ``w - lr * g`` with f32 accumulation and a static
    learning rate, so it only substitutes for stateless SGD; the backend
    gate keeps dense runs on the plain XLA expression (bitwise-stable
    reference) and lets ``backend="pallas"`` opt in to the kernel path.
    """
    return (
        getattr(opt, "name", "") == "sgd"
        and getattr(opt, "lr", None) is not None
        and getattr(backend, "name", "") == "pallas"
    )


def build_local_update(model, opt, *, backend=None, tile_m: int = 1024):
    """Returns ``local_update(params, opt_state, batch) -> (params,
    opt_state, losses)`` over stacked ``(C, ...)`` client trees.

    ``batch`` leaves are ``(C, b, ...)``; ``losses`` is ``(C,)`` per-client
    loss.  One call is one fleet-wide SGD micro-step compiled as a single
    program (vmapped value_and_grad + vmapped optimizer update, or the
    fused-SGD kernel when ``fused_sgd_applicable``, run in the Pallas
    backend's own interpret mode).
    """
    from ..kernels import sgd_update_tree

    use_fused = fused_sgd_applicable(opt, backend)

    def client_grads(p, b):
        return jax.value_and_grad(model.loss)(p, b)

    def local_update(params, opt_state, batch):
        with jax.named_scope(spans.FORWARD_BACKWARD):
            losses, grads = jax.vmap(client_grads)(params, batch)
        with jax.named_scope(spans.OPTIMIZER):
            if use_fused:
                params = sgd_update_tree(
                    params, grads, opt.lr, interpret=backend.interpret, tile_m=tile_m
                )
            else:
                params, opt_state = jax.vmap(opt.update)(params, grads, opt_state)
        return params, opt_state, losses

    return local_update


def build_sequential_local_update(model, opt):
    """Per-client Python-loop reference: ``C`` dispatches per micro-step.

    Same signature and stacked operands as ``build_local_update`` but each
    client's gradient + update runs as its own jitted call on an unstacked
    tree — the dispatch pattern the batched stage replaces.  Kept as the
    baseline for the tokens/sec benchmark and the bitwise-equivalence tests.
    """

    @jax.jit
    def one_client(p, s, b):
        loss, g = jax.value_and_grad(model.loss)(p, b)
        p, s = opt.update(p, g, s)
        return p, s, loss

    def sequential_update(params, opt_state, batch):
        num_clients = jax.tree.leaves(params)[0].shape[0]
        outs = [
            one_client(
                jax.tree.map(lambda x: x[i], params),
                jax.tree.map(lambda x: x[i], opt_state),
                jax.tree.map(lambda x: x[i], batch),
            )
            for i in range(num_clients)
        ]
        params = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[0] for o in outs])
        opt_state = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[1] for o in outs])
        losses = jnp.stack([o[2] for o in outs])
        return params, opt_state, losses

    return sequential_update
