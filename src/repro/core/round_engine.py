"""Whole-round SPMD engine: one jit = one full SD-FEEL protocol round.

`build_fl_train_step` lowers a *single* protocol iteration (the dry-run's
unit).  For production training the dispatch overhead of one jit per
iteration is wasteful, so this engine compiles a full Algorithm-1 round —
``tau1 * tau2`` local iterations with the intra-cluster aggregation applied
every ``tau1`` steps inside a ``lax.scan``, and the inter-cluster gossip once
at the end:

    for j in 1..tau2:          # scanned
        for i in 1..tau1:      #   scanned (local SGD micro-steps)
            W <- W - eta * G
        W <- W @ (V B)         #   intra-cluster aggregation
    W <- W @ (V P^alpha B)     # inter-cluster gossip (round boundary)

Semantics are identical to stepping ``build_fl_train_step`` with the
schedule's events (verified in tests/test_round_engine.py); the batch input
carries a leading round dimension: leaves (tau1*tau2, C, b, ...).

With ``rounds_per_step=R > 1`` the returned step is a *superstep*: an outer
``lax.scan`` over ``R`` full Algorithm-1 rounds compiled as one XLA program,
so a training run becomes a handful of dispatches instead of one per round.
The batch input grows a matching leading dimension
(``R * tau1 * tau2``, C, b, ...) and the semantics are bit-identical to
stepping the ``R = 1`` program ``R`` times (tests/test_runtime.py).

With ``participation=True`` the step gains a fourth operand: a stacked
``(rounds_per_step, C)`` array of per-round intra-cluster weights (one
masked-and-renormalized ``ParticipationPlan`` vector per round), consumed by
the outer scan alongside each round's batches and threaded into every
transition of that round.  The weights are a *traced* input — changing the
drawn subset (or ``k``) changes values only, never the compiled program —
and passing each round's full-participation ``m^`` vector reproduces the
``participation=False`` trajectory (tests/test_participation.py).

With ``mixing=True`` (requires ``participation=True``) the step gains a
fifth operand: a stacked ``(rounds_per_step, D, D)`` per-round mixing-matrix
stack (one faulted/churned eq-5 matrix per round, compiled by
``repro.faults.FaultSchedule.mixing_stack``), scanned alongside the batches
and weights and threaded into each round's *inter* transition.  Like the
weights, the stack is a traced input — link failures, ring→line rewires and
server outages substitute matrix values into one compiled program, never
triggering a recompile (tests/test_faults.py).

The training driver for this engine is ``runtime.RoundScheduler`` — this
module only builds the compiled round step.
"""
from __future__ import annotations

from typing import Any

import jax

from .. import spans
from ..optim import Optimizer
from .sdfeel import FLSpec

PyTree = Any

__all__ = ["build_fl_round_step"]


def _client_axis_constraint(backend):
    """Sharding constraint pinning stacked client trees to the backend's mesh.

    When the selected backend carries a ``jax.sharding.Mesh`` the local
    update phase should run sharded over the clients axis (the same layout
    the shard_map transition consumes), so the compiler never gathers the
    stacked trees between the SGD micro-steps and the aggregation.  Off a
    mesh this is the identity.
    """
    mesh = getattr(backend, "mesh", None)
    if mesh is None:
        return lambda tree: tree
    axis = getattr(backend, "axis_name", None) or "data"
    from jax.sharding import NamedSharding, PartitionSpec

    def constrain(tree):
        def leaf(x):
            spec = PartitionSpec(axis, *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

        return jax.tree.map(leaf, tree)

    return constrain


def build_fl_round_step(model, opt: Optimizer, fl: FLSpec, backend=None,
                        rounds_per_step: int = 1, participation: bool = False,
                        mixing: bool = False, tile_m: int = 1024):
    """Returns round_step(params, opt_state, batches[, weights]) ->
    (params, opt_state, losses).

    ``batches`` leaves: (rounds_per_step * tau1 * tau2, C, per_client_batch,
    ...); ``losses``: (rounds_per_step * tau1 * tau2,) mean loss per
    iteration.  ``backend`` is any ``AggregationBackend`` (default: dense
    Lemma-1 einsum); its traced ``transition`` is inlined into the compiled
    round(s).  With ``participation=True`` the step takes an extra
    ``weights`` operand of shape (rounds_per_step, C): round ``r``'s weight
    vector is applied to every intra/inter transition of that round.  With
    ``mixing=True`` a further ``mixing`` operand of shape
    (rounds_per_step, D, D) supplies round ``r``'s inter-cluster matrix.

    The local-update phase is the shared batched stage from
    ``core.local_update`` — one vmapped program per micro-step, routed
    through the fused-SGD kernel (``tile_m`` tiles) when the backend is
    Pallas and the optimizer is plain SGD.
    """
    from .backends import resolve_backend
    from .local_update import build_local_update

    proto = fl.protocol()
    if backend is None:
        backend = resolve_backend("dense", proto.clusters, proto.P(), fl.alpha)
    tau1, tau2 = fl.tau1, fl.tau2
    if rounds_per_step < 1:
        raise ValueError(f"rounds_per_step must be >= 1, got {rounds_per_step}")
    if mixing and not participation:
        # the fault path always renormalizes per-round weights (crashed
        # clients leave the reduce), so a mixing stack without a weights
        # stack has no caller; keeping one signature shape per flag combo
        raise ValueError("mixing=True requires participation=True")

    local_update = build_local_update(model, opt, backend=backend, tile_m=tile_m)
    constrain = _client_axis_constraint(backend)

    def local_iter(carry, batch):
        params, opt_state = carry
        params, opt_state, losses = local_update(params, opt_state, batch)
        return (params, opt_state), losses.mean()

    def one_round(carry, batches, w=None, p=None):
        carry = (constrain(carry[0]), carry[1])
        # batches leaves: (tau1 * tau2, C, b, ...) — exactly one round's worth;
        # ``w`` is that round's participation weight vector (None == the
        # backend's bound m^, the full-participation fast path)
        seg = jax.tree.map(
            lambda x: x.reshape((tau2, tau1) + x.shape[1:]), batches
        )

        def segment(c, seg_batches):
            # tau1 local iterations then one intra-cluster aggregation
            with jax.named_scope(spans.LOCAL_UPDATE):
                (params, opt_state), losses = jax.lax.scan(local_iter, c, seg_batches)
            with jax.named_scope(spans.TRANSITION_INTRA):
                params = backend.transition(params, "intra", weights=w)
            return (params, opt_state), losses

        (params, opt_state), losses = jax.lax.scan(segment, carry, seg)
        # The last segment applied T_intra = V B; composing with
        # T_inter = V P^a B is exact because B V = I_D (each cluster's
        # aggregate re-aggregates to itself): T_intra @ T_inter = T_inter.
        # Under participation both factors use the same per-round weights, so
        # the composition stays exact round by round.
        with jax.named_scope(spans.TRANSITION_INTER):
            params = backend.transition(params, "inter", weights=w, p=p)
        return (params, opt_state), losses.reshape(tau1 * tau2)

    ipr = tau1 * tau2

    def round_step(params, opt_state, batches):
        (params, opt_state), losses = one_round((params, opt_state), batches)
        return params, opt_state, losses

    def superstep(params, opt_state, batches):
        rounds = jax.tree.map(
            lambda x: x.reshape((rounds_per_step, ipr) + x.shape[1:]), batches
        )
        (params, opt_state), losses = jax.lax.scan(
            one_round, (params, opt_state), rounds
        )
        return params, opt_state, losses.reshape(rounds_per_step * ipr)

    def round_step_p(params, opt_state, batches, weights):
        # weights: (1, C) — same signature as the superstep for one round
        (params, opt_state), losses = one_round(
            (params, opt_state), batches, weights[0]
        )
        return params, opt_state, losses

    def superstep_p(params, opt_state, batches, weights):
        # weights: (rounds_per_step, C), scanned in step with each round
        rounds = jax.tree.map(
            lambda x: x.reshape((rounds_per_step, ipr) + x.shape[1:]), batches
        )

        def body(carry, xs):
            round_batches, w = xs
            return one_round(carry, round_batches, w)

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (rounds, weights)
        )
        return params, opt_state, losses.reshape(rounds_per_step * ipr)

    def round_step_pm(params, opt_state, batches, weights, mixing):
        # weights: (1, C); mixing: (1, D, D)
        (params, opt_state), losses = one_round(
            (params, opt_state), batches, weights[0], mixing[0]
        )
        return params, opt_state, losses

    def superstep_pm(params, opt_state, batches, weights, mixing):
        # mixing: (rounds_per_step, D, D), scanned in step with each round
        rounds = jax.tree.map(
            lambda x: x.reshape((rounds_per_step, ipr) + x.shape[1:]), batches
        )

        def body(carry, xs):
            round_batches, w, p = xs
            return one_round(carry, round_batches, w, p)

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), (rounds, weights, mixing)
        )
        return params, opt_state, losses.reshape(rounds_per_step * ipr)

    if mixing:
        return round_step_pm if rounds_per_step == 1 else superstep_pm
    if participation:
        return round_step_p if rounds_per_step == 1 else superstep_p
    return round_step if rounds_per_step == 1 else superstep
