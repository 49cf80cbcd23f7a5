"""Unified federation runtime: one trainer, pluggable schedulers.

The paper describes three training regimes that previously lived in three
disjoint engines.  ``FederationRuntime`` owns everything they shared —
stacked-parameter init (Algorithm 1 line 1), the jitted eval functions, the
Section V-B wall-clock accounting, eval cadence and ``TrainHistory`` — and
delegates *how a step advances the federation* to a ``Scheduler``:

====================  =====================================================
Scheduler             Paper mapping
====================  =====================================================
``SyncScheduler``     Algorithm 1 / Lemma 1.  Each step is one protocol
                      iteration: vmapped local SGD on every client followed
                      by the scheduled transition ``T_k`` in
                      ``{I, V B, V P^alpha B}`` (eqs. 2-4), applied as the
                      dense einsum or the fused Pallas kernels.
``RoundScheduler``    Whole-round SPMD path.  Each step is ``rounds_per_step``
                      full Algorithm-1 rounds — ``tau1 * tau2`` local
                      iterations with intra-cluster aggregation every
                      ``tau1`` inside a ``lax.scan``, the inter-cluster
                      gossip at each round boundary, and an outer scan over
                      the rounds — compiled as a single XLA program
                      (``round_engine.build_fl_round_step``).
``AsyncScheduler``    Section IV asynchronous SD-FEEL.  Each step pops one
                      edge-cluster event from a wall-clock priority queue,
                      runs deadline-normalized local epochs ``theta_i``
                      (eqs. 18-19), applies the cluster update with gain
                      ``theta_bar_d`` (eq. 20) and the staleness-aware
                      mixing matrix ``P_t`` (eqs. 21-22).
====================  =====================================================

Every scheduler applies the Lemma-1 transition through an injected
``AggregationBackend`` (see ``backends.py``): ``dense`` (paper-faithful
einsum), ``pallas`` (fused TPU kernels), or ``collective`` (hypercube +
ring-ppermute collectives).  The scenario key ``"backend"`` selects one;
``"auto"`` picks by device mesh and cluster-size divisibility::

    runtime = make_run({
        "scheduler": "sync",
        "model": MnistCNN(),
        "clusters": ClusterSpec.uniform(20, 4),
        "topology": "ring",
        "tau1": 5, "alpha": 1,
        "latency": MNIST_LATENCY,
        "backend": "auto",        # or "dense" | "pallas" | "collective"
    })
    history = runtime.run(200, batch_fn, eval_batch, eval_every=20)

All three schedulers execute device-resident: each step is a fused jitted
program with its big operands donated (params/opt_state updated in place),
batches are pre-staged on device by ``pipeline.BatchPipeline`` /
``pipeline.gather_client_batches`` while the previous step computes, and
per-step metrics stay on device until a logging or eval boundary, so the
host never serializes the dispatch pipeline.

Who takes part in a round, with what weights, over what mixing and at what
Section V-B price is one rule, held by ``rounds.FleetRounds``: each
scheduler binds its ``FleetSpec`` (device profile, participation plan,
fault schedule, client-state store) there once and asks it per round for a
``RoundOperands`` record.  The record's traced operands (weights, mixing)
enter the compiled step as arrays, so who participates changes values,
never programs; a fleet with no sampling and no faults runs the static
program, whose weights are the backend's bound ``m^``.

New regimes (e.g. the semi-async deadline sampling of arXiv:2104.12678)
plug in via ``register_scheduler`` and become available to the config-driven
scenario factory ``make_run`` without touching the runtime — and, because
aggregation goes through the backend layer, they inherit every fast path.
"""
from __future__ import annotations

import dataclasses
import heapq
import warnings
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from .backends import collective_supported, resolve_backend
from .config import FleetSpec, RunConfig
from .latency import LatencyModel
from .protocol import SDFEELConfig
from .rounds import FleetRounds
from .staleness import staleness_mixing_matrix
from .topology import TOPOLOGIES, Topology, mixing_matrix

PyTree = Any

__all__ = [
    "TrainHistory",
    "StepEvent",
    "Scheduler",
    "SyncScheduler",
    "RoundScheduler",
    "AsyncScheduler",
    "FederationRuntime",
    "SCHEDULER_REGISTRY",
    "register_scheduler",
    "make_run",
    "stacked_init",
]

_UNSET = object()


def _fleet_from_legacy(fleet: Optional[FleetSpec], owner: str, **legacy) -> FleetSpec:
    """Fold the deprecated per-call ``profile=``/``participation=`` keywords
    into a ``FleetSpec`` (warning once per call site); the factories pass
    ``fleet=`` directly and never hit this path."""
    used = {k: v for k, v in legacy.items() if v is not _UNSET}
    if used:
        warnings.warn(
            f"{owner}({'/'.join(sorted(used))}=...) keywords are deprecated; "
            f"pass fleet=FleetSpec(...) instead",
            DeprecationWarning,
            stacklevel=3,
        )
        fleet = dataclasses.replace(fleet or FleetSpec(), **used)
    return fleet if fleet is not None else FleetSpec()


# ---------------------------------------------------------------------------
# Shared state containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainHistory:
    iterations: list
    wallclock: list
    loss: list
    accuracy: list

    def as_dict(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class StepEvent:
    """What one scheduler step did to the federation.

    ``kind`` is the aggregation event ("local"/"intra"/"inter" for the sync
    path, "round" for a compiled round, "cluster" for an async cluster
    firing, "skipped" for an async event none of whose clients participated).
    ``iteration`` is the protocol-iteration count after the step,
    ``dt`` the Section V-B wall-clock the step consumed.

    ``losses`` (round steps) is left as a *device* array so emitting a step
    never blocks the dispatch pipeline; materialize it with ``float(...)`` /
    ``np.asarray(...)`` only at logging/eval boundaries.
    """

    kind: str
    iteration: int
    dt: float = 0.0
    cluster: Optional[int] = None
    losses: Optional[Any] = None


def stacked_init(model, num_copies: int, seed_or_key) -> PyTree:
    """Identical initial model replicated on a leading axis (Alg. 1 line 1)."""
    key = (
        seed_or_key
        if isinstance(seed_or_key, jax.Array)
        else jax.random.PRNGKey(int(seed_or_key))
    )
    w0 = model.init(key)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_copies,) + x.shape).copy(), w0
    )


@jax.jit
def _contract_clients(params: PyTree, coef) -> PyTree:
    """``sum_c coef[c, ...] * w[c]`` per leaf, in f32, kept in the leaf dtype.

    ``coef`` is ``(C,)`` (consensus) or ``(C, D)`` (cluster stack).  The cast
    back matters at real widths: a bf16 stack contracted to f32 would double
    the serving replicas' device memory.
    """
    coef = jnp.asarray(coef, jnp.float32)
    spec = "c...,c->..." if coef.ndim == 1 else "c...,cd->d..."
    return jax.tree.map(
        lambda w: jnp.einsum(spec, w, coef).astype(w.dtype), params
    )


def _participant_batches(batch_source, k: int, res) -> PyTree:
    """Iteration ``k``'s batches for the resident slots only.

    Sources advertising ``supports_clients`` (e.g. procedural scenario
    sources) produce just the requested rows — O(k_max) per step, the only
    batching path that scales to million-client fleets.  Legacy sources
    produce the full (N, ...) stack host-side and are sliced.
    """
    if getattr(batch_source, "supports_clients", False):
        return batch_source(k, clients=res.clients)
    full = batch_source(k)
    return jax.tree.map(lambda x: np.asarray(x)[res.clients], full)


# ---------------------------------------------------------------------------
# Scheduler protocol
# ---------------------------------------------------------------------------

@runtime_checkable
class Scheduler(Protocol):
    """Pluggable federation schedule.

    ``bind`` receives the model and seed once (build jitted steps, init
    stacked params); ``step`` advances the federation by one schedule unit
    given the runtime's batch source; ``global_params`` extracts the
    consensus-phase model.
    """

    name: str

    def bind(self, model, seed: int) -> None: ...

    def step(self, k: int, batch_source) -> StepEvent: ...

    def global_params(self) -> PyTree: ...


# ---------------------------------------------------------------------------
# Synchronous per-iteration scheduler (Algorithm 1)
# ---------------------------------------------------------------------------

def _legacy_impl_backend(impl: str, clusters, p) -> str:
    """Map the legacy ``aggregation_impl``/``impl`` field to a backend name.

    ``"gossip"`` historically fell back to the dense einsum in the host-loop
    schedulers (it was only honored inside ``build_fl_train_step``), so it
    maps to the collective backend only when the scenario satisfies its
    constraints and degrades to dense otherwise — old configs keep working.
    """
    if impl == "gossip":
        return "collective" if collective_supported(clusters, p) else "dense"
    return {"dense": "dense", "pallas": "pallas"}[impl]


def _bind_backend(sched, impl: str, clusters, proto, state):
    """Resolve ``sched``'s mesh and aggregation backend over ``clusters``.

    Returns ``state`` (the resident client stack, or ``None`` offloaded)
    sharded onto the backend's client mesh when it has one.  Under faults,
    the collective backend checks the whole fault horizon host-side once:
    traced mixing values cannot be checked on device.
    """
    from ..launch.mesh import resolve_client_mesh, shard_clients

    spec = sched._backend_spec
    if spec is None:
        # honor impl="gossip" only where the collective path is valid
        spec = _legacy_impl_backend(impl, clusters, proto.P())
    sched.mesh = resolve_client_mesh(sched._mesh_spec, clusters.num_clients)
    sched.backend = resolve_backend(
        spec, clusters, proto.P(), proto.alpha, mesh=sched.mesh
    )
    if getattr(sched.backend, "mesh", None) is not None and state is not None:
        state = shard_clients(state, sched.mesh, sched.backend.axis_name)
    faults = sched.rounds.faults
    if faults is not None and sched.backend.name == "collective":
        faults.mixing_stack(0, faults.horizon() + 1, require_ring_stencil=True)
    return state


class SyncScheduler:
    """Algorithm 1 over stacked client models (host loop, CPU-friendly).

    ``batch_source`` contract: callable ``k -> stacked batch`` with leaves of
    shape (C, per_client_batch, ...).  ``backend`` is an
    ``AggregationBackend`` name/instance (or ``"auto"``); when omitted it is
    derived from the legacy ``cfg.aggregation_impl`` field.

    Each protocol iteration is ONE donated XLA dispatch: the vmapped local
    SGD step and the scheduled Lemma-1 transition are fused into a single
    jitted function cached per event kind, and the stacked params are donated
    so the update happens in place.  ``step`` stages batches through a
    :class:`~repro.core.pipeline.BatchPipeline`, overlapping host batch prep
    with the in-flight device step (``prefetch=False`` restores the
    host-synchronous seed behavior — only useful as a benchmark baseline).

    ``fleet`` (a ``repro.core.config.FleetSpec``) carries the who-axis as one
    object, bound in ``self.rounds`` (:class:`~repro.core.rounds.FleetRounds`):
    each iteration takes its round's (one round = ``tau1 * tau2``
    iterations) record of traced operands and is priced by the round's
    pacing clients.

    With a ``host-offload`` store the scheduler runs on a fixed ``(k_max,
    ...)`` participant buffer: gathered at each round start, stepped through
    the same fused programs (built over the store's sub-fleet), scattered
    back at the round's inter-cluster boundary.  The legacy ``profile=`` /
    ``participation=`` keywords still work but emit a ``DeprecationWarning``.
    """

    name = "sync"

    def __init__(self, cfg: SDFEELConfig, latency: Optional[LatencyModel] = None,
                 backend=None, profile=_UNSET, prefetch: bool = True,
                 participation=_UNSET, fleet: Optional[FleetSpec] = None,
                 mesh=None):
        self.cfg = cfg
        self.latency = latency
        self._mesh_spec = mesh
        self.fleet = _fleet_from_legacy(
            fleet, "SyncScheduler", profile=profile, participation=participation
        )
        self.profile = self.fleet.resolve_profile(cfg.clusters.num_clients)
        self.rounds = FleetRounds(self.fleet, cfg, latency, self.profile)
        self.prefetch = prefetch
        self.params: PyTree = None
        self._backend_spec = backend
        self.plan = self.store = self.faults = None
        self._pipeline = None
        self._pipeline_src = None

    def bind(self, model, seed: int) -> None:
        cfg = self.cfg
        self.model = model
        rounds = self.rounds
        rounds.bind(seed, rows=cfg.clusters.num_clients)
        self.plan, self.faults, self.store = rounds.plan, rounds.faults, rounds.store
        self._m = jnp.asarray(cfg.clusters.m(), jnp.float32)
        self._buffer = self._buf_round = self._res = None
        if self.store.resident:
            self.params = stacked_init(model, cfg.clusters.num_clients, seed)
            self.store.attach(self)
            agg_clusters = cfg.clusters
        else:
            # fixed (k_max, ...) participant buffer; the aggregation runs
            # over the store's sub-fleet (same clusters, slot-sized)
            self.store.bind(cfg.clusters, model, seed)
            agg_clusters = self.store.sub_clusters
        self.params = _bind_backend(
            self, cfg.aggregation_impl, agg_clusters, cfg, self.params
        )
        from .. import optim
        from .local_update import build_local_update

        # shared batched stage: one vmapped value_and_grad + SGD update per
        # micro-step (fp32/bf16 math identical to the former inline p - lr*g)
        local_stage = build_local_update(
            model, optim.sgd(cfg.learning_rate), backend=self.backend
        )

        def local_sgd(params, batch):
            with jax.named_scope(spans.LOCAL_UPDATE):
                params, _, _ = local_stage(params, (), batch)
            return params

        transition_scope = {"intra": spans.TRANSITION_INTRA,
                            "inter": spans.TRANSITION_INTER}

        def make_step(event):
            def transition(params, **kw):
                if event == "local":
                    return params
                with jax.named_scope(transition_scope[event]):
                    return self.backend.transition(params, event, **kw)

            def fused(params, batch):
                return transition(local_sgd(params, batch))

            def fused_sampled(params, batch, weights):
                return transition(local_sgd(params, batch), weights=weights)

            def fused_faulted(params, batch, weights, p):
                # p is consumed only by the inter transition (backends ignore
                # it elsewhere); weights fold crashed clients/uplink drops
                # into the same renormalized vector participation uses
                return transition(local_sgd(params, batch), weights=weights, p=p)

            fn = {"static": fused, "weights": fused_sampled,
                  "mixing": fused_faulted}[rounds.variant]
            return jax.jit(fn, donate_argnums=0)

        self._step_fns = {e: make_step(e) for e in ("local", "intra", "inter")}

    def _round_of(self, k: int) -> int:
        return (k - 1) // (self.cfg.tau1 * self.cfg.tau2)

    # -- one protocol iteration (local + scheduled aggregation) -------------
    def _apply(self, k: int, staged_batch) -> tuple[str, float]:
        event = self.cfg.event_at(k)
        r = self._round_of(k)
        resident = self.store.resident
        if not resident and (self._buffer is None or self._buf_round != r):
            self._res = self.rounds.residency(r)
            self._buffer = self.store.gather(self._res)
            self._buf_round = r
        ops = self.rounds.operands(r, self._res)
        state = self._step_fns[event](
            self.params if resident else self._buffer, staged_batch, *ops.args
        )
        dt = self.rounds.event_time(event, ops)
        if resident:
            self.params = state
        else:
            self._buffer = state
        if event == "inter":
            dt += ops.penalty
            if not resident:
                # round boundary: every resident's state is its cluster's
                # post-gossip aggregate — fully representable by the store
                self.store.scatter(self._res, self._buffer)
                self._buffer = None
        return event, dt

    def advance(self, k: int, stacked_batch: dict) -> str:
        if not self.store.resident:
            res = self.rounds.residency(self._round_of(k))
            stacked_batch = jax.tree.map(
                lambda x: np.asarray(x)[res.clients], stacked_batch
            )
        return self._apply(k, jax.tree.map(jnp.asarray, stacked_batch))[0]

    def iteration_time(self, event: str) -> float:
        """Full-fleet §V-B pacing (participation-masked rounds may be cheaper)."""
        return self.rounds.event_time(event)

    def _next_batch(self, k: int, batch_source) -> PyTree:
        from .pipeline import BatchPipeline, device_batch

        if self.store.resident:
            producer = batch_source
        else:
            def producer(i: int) -> PyTree:
                res = self.rounds.residency(self._round_of(i))
                return _participant_batches(batch_source, i, res)

        if not self.prefetch:
            return device_batch(producer(k))
        if (self._pipeline is None or self._pipeline_src is not batch_source
                or self._pipeline.next_index != k):
            self._pipeline = BatchPipeline(producer, start=k)
            self._pipeline_src = batch_source
        return self._pipeline.get(k)

    def step(self, k: int, batch_source) -> StepEvent:
        event, dt = self._apply(k, self._next_batch(k, batch_source))
        return StepEvent(kind=event, iteration=k, dt=dt)

    def global_params(self) -> PyTree:
        """Consensus-phase output: sum_d m~_d y_K^(d) == sum_i m_i w_K^(i)."""
        if self.store.resident:
            return _contract_clients(self.params, self._m)
        if self._buffer is None:
            return self.store.global_params()
        # mid-round: residents' live buffer + the store's cold majority
        return self.store.global_params(resident=self._res, buffer=self._buffer)

    def cluster_params(self) -> PyTree:
        """Stacked ``(D, ...)`` per-cluster models y^(d) = sum_{i in d} m^_i w^(i).

        This is what ``serving.FederatedServer`` hot-swaps at round
        boundaries — the personalized models the intra-cluster aggregation
        maintains, as opposed to the ``global_params`` consensus.
        """
        if not self.store.resident:
            raise NotImplementedError(
                "cluster_params requires a resident client-state store; "
                "serve host-offload runs from checkpoints instead"
            )
        return _contract_clients(self.params, self.cfg.clusters.V())


# ---------------------------------------------------------------------------
# Whole-round compiled scheduler (production SPMD path)
# ---------------------------------------------------------------------------

class RoundScheduler:
    """One step == ``rounds_per_step`` scan-compiled tau1*tau2 Algorithm-1 rounds.

    ``batch_source`` contract: callable ``k -> stacked batch`` indexed by the
    *protocol iteration* — step ``r`` consumes iterations
    ``(r-1)*R*tau1*tau2 + 1 .. r*R*tau1*tau2`` for ``R = rounds_per_step``.

    This is the device-resident fast path: each step is one donated XLA
    dispatch covering ``R`` full Algorithm-1 rounds (an outer ``lax.scan`` in
    ``round_engine.build_fl_round_step``), the stacked params/opt_state are
    donated so the federation state is updated in place, the next superstep's
    batches are pre-stacked and transferred by a
    :class:`~repro.core.pipeline.BatchPipeline` while the current one
    computes, and ``StepEvent.losses`` stays a device array so the host never
    blocks on metrics between supersteps (materialize with ``float``/
    ``np.asarray`` at logging boundaries).

    ``fleet`` (a ``FleetSpec``) carries profile/participation/faults/store
    as one object, bound in ``self.rounds`` (a ``FleetRounds``; the old
    ``profile=``/``participation=`` keywords warn).  With a
    ``host-offload`` store the superstep engine is compiled over the fixed
    ``(k_max, ...)`` slot buffer: one participation draw per superstep picks
    the residents, their batches and stageable host rows prefetch together,
    and gather -> superstep -> scatter bounds device memory by ``k_max``
    regardless of ``num_clients``.  Under offload, stateful optimizers reset
    between supersteps (plain SGD — the paper's setting — is unaffected).
    """

    name = "round"

    def __init__(self, fl, optimizer=None, latency: Optional[LatencyModel] = None,
                 backend=None, profile=_UNSET, rounds_per_step: int = 1,
                 prefetch: bool = True, participation=_UNSET,
                 fleet: Optional[FleetSpec] = None, mesh=None):
        if rounds_per_step < 1:
            raise ValueError(f"rounds_per_step must be >= 1, got {rounds_per_step}")
        self.fl = fl
        self.optimizer = optimizer
        self.latency = latency
        self._mesh_spec = mesh
        self.fleet = _fleet_from_legacy(
            fleet, "RoundScheduler", profile=profile, participation=participation
        )
        self.profile = self.fleet.resolve_profile(fl.num_clients)
        self._proto = fl.protocol()
        self.rounds = FleetRounds(self.fleet, self._proto, latency, self.profile)
        self.rounds_per_step = rounds_per_step
        self.prefetch = prefetch
        self.params: PyTree = None
        self.opt_state: PyTree = None
        self._backend_spec = backend
        self.plan = self.store = self.faults = None
        self._pipeline = None
        self._pipeline_src = None
        self._res_cache = None  # (step k, Residency) — prefetch must agree

    @property
    def iterations_per_round(self) -> int:
        return self.fl.tau1 * self.fl.tau2

    @property
    def iterations_per_step(self) -> int:
        """Protocol iterations consumed by one (super)step."""
        return self.iterations_per_round * self.rounds_per_step

    def rounds_for(self, iterations: int) -> int:
        """Whole compiled rounds covering ``iterations`` protocol iterations."""
        return max(1, -(-iterations // self.iterations_per_round))

    def steps_for(self, iterations: int) -> int:
        """Scheduler steps (superstep dispatches) covering ``iterations``."""
        return -(-self.rounds_for(iterations) // self.rounds_per_step)

    def bind(self, model, seed: int) -> None:
        from .. import optim
        from .round_engine import build_fl_round_step

        self.model = model
        fl = self.fl
        opt = self.optimizer or optim.sgd(fl.learning_rate)
        self.optimizer = opt
        rounds = self.rounds
        # offloaded slots always carry weights: padding slots weigh exactly 0
        rounds.bind(seed, rows=fl.num_clients, weighted_offload=True)
        self.plan, self.faults, self.store = rounds.plan, rounds.faults, rounds.store
        if self.store.resident:
            self.params = stacked_init(model, fl.num_clients, seed)
            self.opt_state = opt.init(self.params)
            self.store.attach(self)
            engine_fl = fl
            agg_clusters = self._proto.clusters
            state = (self.params, self.opt_state)
        else:
            # superstep engine compiled over the store's (k_max, ...) slots
            self.store.bind(self._proto.clusters, model, seed)
            engine_fl = dataclasses.replace(fl, num_clients=self.store.k_max)
            agg_clusters = self.store.sub_clusters
            state = None
        state = _bind_backend(self, fl.impl, agg_clusters, self._proto, state)
        if state is not None:
            self.params, self.opt_state = state
        self._round_step = jax.jit(
            build_fl_round_step(model, opt, engine_fl, backend=self.backend,
                                rounds_per_step=self.rounds_per_step,
                                participation=rounds.variant != "static",
                                mixing=rounds.variant == "mixing"),
            donate_argnums=(0, 1),
        )

    def round_time(self) -> float:
        """Section V-B wall-clock of one full round of the full fleet."""
        return self.rounds.round_time()

    # -- residency (host-offload stores) -------------------------------------
    def _residency_for_step(self, k: int):
        """Superstep ``k``'s slot assignment — one participation draw per
        superstep (round ``(k-1)*R``'s mask covers all ``R`` scanned rounds),
        deterministic in ``k`` so prefetch and execution agree."""
        if self._res_cache is None or self._res_cache[0] != k:
            self._res_cache = (k, self.rounds.residency((k - 1) * self.rounds_per_step))
        return self._res_cache[1]

    def _superstep_batches(self, k: int, batch_source):
        from .pipeline import BatchPipeline, device_batch, stack_window

        ips = self.iterations_per_step

        if self.store.resident:
            def producer(step_idx: int) -> PyTree:
                return stack_window(batch_source, (step_idx - 1) * ips + 1, ips)

            transfer = device_batch
        else:
            # participant batches and stageable host state rows prefetch
            # together, while the previous superstep still runs on device
            def producer(step_idx: int):
                res = self._residency_for_step(step_idx)
                window = stack_window(
                    lambda i: _participant_batches(batch_source, i, res),
                    (step_idx - 1) * ips + 1, ips,
                )
                in_flight = (
                    self._residency_for_step(step_idx - 1) if step_idx > 1
                    else None
                )
                return window, self.store.stage(res, in_flight=in_flight)

            def transfer(item):
                window, staged = item
                return device_batch(window), staged

        if not self.prefetch:
            return transfer(producer(k))
        if (self._pipeline is None or self._pipeline_src is not batch_source
                or self._pipeline.next_index != k):
            self._pipeline = BatchPipeline(producer, start=k, transfer=transfer)
            self._pipeline_src = batch_source
        return self._pipeline.get(k)

    def step(self, k: int, batch_source) -> StepEvent:
        resident = self.store.resident
        with jax.profiler.TraceAnnotation(spans.STAGE):
            stacked = self._superstep_batches(k, batch_source)
            res = None
            if not resident:
                stacked, staged = stacked
                res = self._residency_for_step(k)
                buf = self.store.gather(res, staged)
        if resident:
            state, opt_state = self.params, self.opt_state
        else:
            # sgd's state is () so per-superstep re-init is free; stateful
            # optimizers reset between supersteps under offload (documented)
            state, opt_state = buf, self.optimizer.init(buf)
        # rounds (k-1)*R .. k*R-1, one record per scanned round; their
        # operands stack to traced (R, ...) arrays, so redraws never recompile
        r0 = (k - 1) * self.rounds_per_step
        records = [self.rounds.operands(r0 + i, res, draw=r0)
                   for i in range(self.rounds_per_step)]
        args = tuple(jnp.stack(col) for col in zip(*(ops.args for ops in records)))
        with jax.profiler.TraceAnnotation(spans.DISPATCH):
            state, opt_state, losses = self._round_step(
                state, opt_state, stacked, *args
            )
        if resident:
            self.params, self.opt_state = state, opt_state
        else:
            self.store.scatter(res, state)
        return StepEvent(
            kind="round",
            iteration=k * self.iterations_per_step,
            dt=self.rounds.superstep_time(records),
            losses=losses,
        )

    def global_params(self) -> PyTree:
        if not self.store.resident:
            # supersteps scatter before returning, so the store is the truth
            return self.store.global_params()
        return _contract_clients(self.params, self._proto.clusters.m())

    def cluster_params(self) -> PyTree:
        """Stacked ``(D, ...)`` per-cluster models at the last round boundary.

        Steps end on the inter-cluster gossip, so every client of cluster
        ``d`` holds y^(d) and the V^T contraction is exact — this is the
        stack ``serving.FederatedServer`` hot-swaps between batches.
        """
        if not self.store.resident:
            raise NotImplementedError(
                "cluster_params requires a resident client-state store; "
                "serve host-offload runs from checkpoints instead"
            )
        return _contract_clients(self.params, self._proto.clusters.V())


# ---------------------------------------------------------------------------
# Asynchronous event-driven scheduler (Section IV)
# ---------------------------------------------------------------------------

class AsyncScheduler:
    """Priority-queue cluster events with staleness-aware mixing.

    ``batch_source`` contract: an object with ``next_batch(client) -> batch``
    (e.g. ``repro.data.ClientBatcher``); sources additionally exposing the
    bulk ``next_batches(clients, count)`` skip the per-client Python loop
    entirely (see ``pipeline.gather_client_batches``).  The eq. 21-22
    staleness mixing ``P_t`` is applied through ``backend.inter_cluster``, so
    the async path inherits whichever optimized mixing path the backend
    provides.

    The eq. 20 cluster update runs as one donated dispatch over the full
    stacked ``y`` (the fired cluster enters as a traced dynamic index), and
    because the queue already determines the next event when a step finishes,
    the next cluster's batch gather is staged while the device is still
    executing the current update (``prefetch=False`` disables the overlap).

    ``participation`` samples who contributes to each cluster event: the
    fired cluster's eq. 20 weights are masked to the event's participants
    and renormalized (a sampled-out client's update is *skipped*, not merged
    stale — its weight is exactly 0), entering the donated update as traced
    values.  When none of the cluster's members participate the event is
    skipped outright (``StepEvent.kind == "skipped"``): no update, no
    staleness mixing, no protocol-iteration increment — the cluster's gap
    simply keeps growing while the wall-clock advances.
    """

    name = "async"

    def __init__(self, cfg, backend=None, prefetch: bool = True,
                 participation=_UNSET, fleet: Optional[FleetSpec] = None):
        self.cfg = cfg
        self.prefetch = prefetch
        self._backend_spec = backend
        self.fleet = _fleet_from_legacy(
            fleet, "AsyncScheduler", participation=participation
        )
        self.rounds = FleetRounds(self.fleet, cfg, cfg.alpha_latency, cfg.profile)
        self.plan = self.store = self.faults = None
        self._prefetched = None

    def bind(self, model, seed: int) -> None:
        from .protocol import ClusterSpec

        cfg = self.cfg
        self.model = model
        self.theta = cfg.theta()
        self.iter_times = cfg.iter_times()
        timing = self.rounds.timing
        self._dropout = None
        if timing is not None and np.any(cfg.profile.availability < 1.0):
            self._dropout = timing.dropout_process(cfg.clusters, seed=seed)
        d = cfg.clusters.num_clusters
        # per-cluster models, stacked (D, ...).  The async device state is
        # already cluster-sized, so a host-offload store wraps the y-stack as
        # one pseudo "client" per cluster (mass m~_d) in identity residency —
        # same store API, no residency smaller than D to exploit.
        self.store = self.fleet.resolve_store(d)
        self.y = stacked_init(model, d, seed)
        if self.store.resident:
            self.store.attach(self, "y")
            self._store_res = None
        else:
            if self.store.k_max not in (None, d):
                raise ValueError(
                    f"async state is per-cluster: a host-offload store must "
                    f"cover all {d} clusters (k_max in (None, {d})), got "
                    f"k_max={self.store.k_max}"
                )
            sizes = np.zeros(d)
            np.add.at(
                sizes,
                np.asarray(cfg.clusters.assignments, dtype=np.int64),
                np.asarray(cfg.clusters.data_sizes, dtype=np.float64),
            )
            pseudo = ClusterSpec(d, tuple(range(d)), tuple(float(x) for x in sizes))
            self.store.bind(pseudo, model, seed)
            self._store_res = self.store.residency()
            self.y = self.store.gather(self._store_res)
        self.t = 0
        self.last_update = np.zeros(d, dtype=np.int64)  # t'(d)
        self.clock = 0.0
        self._queue: list[tuple[float, int]] = [
            (self.iter_times[j], j) for j in range(d)
        ]
        heapq.heapify(self._queue)
        self._m_tilde = jnp.asarray(cfg.clusters.m_tilde(), jnp.float32)
        lr = cfg.learning_rate
        self._theta_max = theta_max = int(self.theta.max())
        # per-cluster constants staged once instead of per event
        self._thetas = [
            jnp.asarray(self.theta[cfg.clusters.clients_of(j)], jnp.int32)
            for j in range(d)
        ]
        self._m_hats = [
            jnp.asarray(cfg.clusters.m_hat()[cfg.clusters.clients_of(j)], jnp.float32)
            for j in range(d)
        ]
        # the async fault axis is indexed by the global iteration count t —
        # the same granularity the eq. 21-22 gaps are measured in
        self.rounds.bind(seed)
        self.plan, self.faults = self.rounds.plan, self.rounds.faults
        self._client_idx = [
            np.asarray(cfg.clusters.clients_of(j)) for j in range(d)
        ]
        self._m_hat_np = cfg.clusters.m_hat()

        def client_delta(params, batches, theta_i):
            """theta_i masked local epochs; returns normalized update (eq 19)."""

            def step(w, inp):
                b, step_idx = inp
                g = jax.grad(model.loss)(w, b)
                mask = (step_idx < theta_i).astype(jnp.float32)
                return jax.tree.map(lambda wi, gi: wi - lr * mask * gi, w, g), None

            w_final, _ = jax.lax.scan(
                step, params, (batches, jnp.arange(theta_max, dtype=jnp.int32))
            )
            return jax.tree.map(
                lambda wf, w0_: (wf - w0_) / theta_i.astype(jnp.float32), w_final, params
            )

        def cluster_update(y, d_idx, batches, thetas, m_hat):
            """eq. 20 over the full stack: y[d] <- y[d] + theta_bar sum m^ Delta.

            ``y`` is donated (updated in place); ``d_idx`` is a traced index,
            so one compiled program serves every cluster of a given size.
            """
            y_d = jax.tree.map(lambda w: w[d_idx], y)
            deltas = jax.vmap(client_delta, in_axes=(None, 0, 0))(y_d, batches, thetas)
            theta_bar = jnp.sum(m_hat * thetas.astype(jnp.float32))
            return jax.tree.map(
                lambda w, yd, dl: w.at[d_idx].set(
                    yd + theta_bar * jnp.einsum("c...,c->...", dl, m_hat)
                ),
                y,
                y_d,
                deltas,
            )

        self._cluster_update = jax.jit(cluster_update, donate_argnums=0)
        self.backend = resolve_backend(
            self._backend_spec, cfg.clusters,
            mixing_matrix(cfg.topology, cfg.clusters.m_tilde()), 1,
        )

        def global_model(y):
            return jax.tree.map(lambda w: jnp.einsum("d...,d->...", w, self._m_tilde), y)

        self._global = jax.jit(global_model)

    def _gather(self, batch_source, d: int) -> PyTree:
        """Bulk per-client gather for cluster ``d``, staged on device."""
        from .pipeline import device_batch, gather_client_batches

        return device_batch(gather_client_batches(
            batch_source, self.cfg.clusters.clients_of(d), self._theta_max
        ))

    def _event_weights(self, k: int, d: int):
        """(m_hat jnp, participated) for event ``k`` on cluster ``d``.

        The event index seeds the draw (deterministic, order-independent);
        the fired cluster's ``m^`` sub-vector is masked to the participants
        and renormalized, so non-participants carry weight exactly 0 in the
        eq. 20 update.  All-masked clusters report ``participated=False``.

        Under a fault schedule the mask additionally drops crashed clients
        and this iteration's uplink failures (round axis = the global
        iteration count ``t``).
        """
        idx = self._client_idx[d]
        mask = self.rounds.client_mask(k - 1, fault_r=self.t)[idx]
        if not mask.any():
            return None, False
        w = np.where(mask, self._m_hat_np[idx], 0.0)
        return jnp.asarray(w / w.sum(), jnp.float32), True

    def step(self, k: int, batch_source) -> StepEvent:
        cfg = self.cfg
        prev_clock = self.clock
        self.clock, d = heapq.heappop(self._queue)

        # theta_max batches per client (masked beyond theta_i); usually staged
        # by the previous step's prefetch while the device was busy.  Gathered
        # even for skipped events so the batch streams stay identical across
        # prefetch settings and participation draws.
        if (self._prefetched is not None and self._prefetched[0] is batch_source
                and self._prefetched[1] == d):
            batches = self._prefetched[2]
        else:
            batches = self._gather(batch_source, d)
        self._prefetched = None

        # A dead edge server fires nothing: its cluster idles (kind "outage",
        # no update, no mixing, t unchanged) and re-enters via the staleness
        # mixing once it is back — the gap keeps growing through the outage,
        # so psi discounts the stale model exactly as eq. 22 prescribes.
        r_fault = self.t  # the fault round this event runs in (pre-increment)
        outage = (
            self.faults is not None
            and not bool(self.faults.server_alive(r_fault)[d])
        )
        m_hat, participated = (
            self._event_weights(k, d)
            if (self.rounds.sampling or self.faults is not None)
            else (self._m_hats[d], True)
        )
        if outage:
            participated = False
        if participated:
            self.y = self._cluster_update(
                self.y, d, batches, self._thetas[d], m_hat
            )

            # staleness-aware inter-cluster mixing (eq. 21-22) via the
            # backend, over the round's *surviving* edge set under faults —
            # a downed link drops its neighbor from the blend
            gaps = (self.t - self.last_update).astype(np.float64)
            gaps[d] = 0.0
            graph = (
                cfg.topology if self.faults is None
                else self.faults.adjacency_at(r_fault)
            )
            p_t = staleness_mixing_matrix(graph, d, gaps, cfg.psi)
            self.y = self.backend.inter_cluster(
                self.y, jnp.asarray(p_t, jnp.float32), 1
            )

            self.t += 1
            self.last_update[d] = self.t
            if not self.store.resident:
                # device-side take on the identity map — keeps the store's
                # persistent cluster stack in lockstep with the live y
                self.store.scatter(self._store_res, self.y)
        # Next firing: service time, stretched by dropout retries when the
        # profile says some of the cluster's devices are flaky, plus the
        # capped-backoff retries of this iteration's failed uplinks.
        service = self.iter_times[d]
        if self._dropout is not None:
            service *= self._dropout.attempts(d)
        timing = self.rounds.timing
        if self.faults is not None and timing is not None and not outage:
            idx = self._client_idx[d]
            failed = np.zeros(cfg.clusters.num_clients, dtype=bool)
            failed[idx] = self.faults.uplink_failed(r_fault)[idx]
            service += timing.uplink_retry_penalty(failed)
        heapq.heappush(self._queue, (self.clock + service, d))
        if self.prefetch:
            # the queue top IS the next event — gather its batches now, while
            # the dispatched update/mixing still run on device
            nxt = self._queue[0][1]
            self._prefetched = (batch_source, nxt, self._gather(batch_source, nxt))
        return StepEvent(
            kind=("outage" if outage
                  else "cluster" if participated else "skipped"),
            iteration=self.t, dt=self.clock - prev_clock, cluster=d,
        )

    def global_params(self) -> PyTree:
        if not self.store.resident:
            return self.store.global_params()
        return self._global(self.y)

    def cluster_params(self) -> PyTree:
        """Stacked ``(D, ...)`` per-cluster models — the async state itself.

        The event queue maintains ``y`` cluster-stacked (eq. 20-22 update it
        in place), so personalized serving reads it directly; consumers that
        outlive a step must copy (the next event donates these buffers),
        which ``serving.FederatedServer.publish`` does.
        """
        return self.y


# ---------------------------------------------------------------------------
# The runtime
# ---------------------------------------------------------------------------

class FederationRuntime:
    """Event-driven federated trainer parameterized by a ``Scheduler``.

    Owns the pieces every regime shares: parameter init (delegated to the
    scheduler's ``bind``), the jitted eval functions, the wall-clock
    accumulator, eval cadence and ``TrainHistory`` assembly.
    """

    def __init__(self, model, scheduler: Scheduler, seed: int = 0):
        self.model = model
        self.scheduler = scheduler
        self.clock = 0.0
        self.iteration = 0
        self._k = 0
        scheduler.bind(model, seed)
        has_acc = hasattr(model, "accuracy")

        def eval_fn(p, b):
            # loss + accuracy fused into one program -> one blocking transfer
            return model.loss(p, b), (model.accuracy(p, b) if has_acc else None)

        self._eval_fn = jax.jit(eval_fn)
        self._eval_batch_cache: Optional[tuple] = None

    def step(self, batch_source) -> StepEvent:
        """Advance the federation by one schedule unit."""
        self._k += 1
        ev = self.scheduler.step(self._k, batch_source)
        self.clock += ev.dt
        self.iteration = ev.iteration
        return ev

    def global_params(self) -> PyTree:
        return self.scheduler.global_params()

    def cluster_params(self) -> PyTree:
        """Stacked ``(D, ...)`` per-cluster personalized models.

        The training→serving hook: ``serving.FederatedServer`` publishes
        this stack at round boundaries to serve each edge cluster its own
        model while training continues.
        """
        fn = getattr(self.scheduler, "cluster_params", None)
        if fn is None:
            raise NotImplementedError(
                f"scheduler {self.scheduler.name!r} does not expose "
                "per-cluster models"
            )
        return fn()

    def evaluate(self, eval_batch) -> tuple[float, Optional[float]]:
        g = self.global_params()
        # the eval batch rarely changes between calls — upload it once; the
        # key includes every leaf's identity so replacing an entry of the
        # same dict in place still invalidates the cached device copy
        key = (id(eval_batch), tuple(id(l) for l in jax.tree.leaves(eval_batch)))
        cache = self._eval_batch_cache
        if cache is None or cache[0] != key:
            cache = (key, eval_batch, jax.tree.map(jnp.asarray, eval_batch))
            self._eval_batch_cache = cache
        loss, acc = jax.device_get(self._eval_fn(g, cache[2]))
        return float(loss), (None if acc is None else float(acc))

    def run(
        self,
        num_steps: int,
        batch_source,
        eval_batch=None,
        eval_every: int = 50,
    ) -> TrainHistory:
        """Run ``num_steps`` schedule units, evaluating every ``eval_every``.

        ``wallclock`` entries use the scheduler's absolute ``clock`` when it
        keeps one (the async event queue is keyed by absolute finish times,
        so time spent in earlier manual ``step`` calls is included); schedule
        types without their own clock report time relative to this call.
        """
        hist = TrainHistory([], [], [], [])
        self._k = 0
        self.clock = 0.0
        for e in range(1, num_steps + 1):
            self.step(batch_source)
            if eval_batch is not None and (e % eval_every == 0 or e == num_steps):
                loss, acc = self.evaluate(eval_batch)
                hist.iterations.append(self.iteration)
                hist.wallclock.append(getattr(self.scheduler, "clock", self.clock))
                hist.loss.append(loss)
                if acc is not None:
                    hist.accuracy.append(acc)
        return hist


# ---------------------------------------------------------------------------
# Config-driven scenario registry
# ---------------------------------------------------------------------------

SCHEDULER_REGISTRY: dict[str, Callable[[dict], Scheduler]] = {}


def register_scheduler(name: str):
    """Register a scenario factory: ``dict -> Scheduler``.

    This is the plugin point for new regimes — a semi-async deadline sampler
    is a ~100-line scheduler class plus one ``@register_scheduler`` factory.
    """

    def deco(factory: Callable[[dict], Scheduler]):
        SCHEDULER_REGISTRY[name] = factory
        return factory

    return deco


def _as_topology(topo, num_clusters: int) -> Topology:
    if isinstance(topo, Topology):
        return topo
    return TOPOLOGIES[topo](num_clusters)


def _as_clusters(s: dict):
    from .protocol import ClusterSpec

    clusters = s.pop("clusters", None)
    if clusters is not None:
        return clusters
    return ClusterSpec.uniform(s.pop("num_clients"), s.pop("num_clusters"))


def _as_fleet(s: dict) -> FleetSpec:
    """Pop the who-axis keys into one ``FleetSpec``.

    Accepts either a ready ``"fleet"`` entry (``FleetSpec`` or kwargs dict)
    or the flat ``profile``/``profile_seed``/``participation``/``store``
    keys that ``RunConfig.to_dict`` emits.
    """
    fleet = s.pop("fleet", None)
    if fleet is not None:
        if not isinstance(fleet, FleetSpec):
            fleet = FleetSpec(**dict(fleet))
        return fleet
    return FleetSpec(
        profile=s.pop("profile", None),
        profile_seed=s.pop("profile_seed", None),
        participation=s.pop("participation", None),
        store=s.pop("store", None),
        faults=s.pop("faults", None),
    )


@register_scheduler("sync")
def _make_sync(s: dict) -> SyncScheduler:
    clusters = _as_clusters(s)
    topology = _as_topology(s.pop("topology", "ring"), clusters.num_clusters)
    fleet = _as_fleet(s)
    cfg = SDFEELConfig(
        clusters=clusters,
        topology=topology,
        tau1=s.pop("tau1", 5),
        tau2=s.pop("tau2", 1),
        alpha=s.pop("alpha", 1),
        learning_rate=s.pop("learning_rate", 0.01),
        aggregation_impl=s.pop("aggregation_impl", "dense"),
    )
    return SyncScheduler(
        cfg, latency=s.pop("latency", None), backend=s.pop("backend", None),
        prefetch=s.pop("prefetch", True), fleet=fleet,
        mesh=s.pop("mesh", None),
    )


@register_scheduler("round")
def _make_round(s: dict) -> RoundScheduler:
    from .sdfeel import FLSpec

    fleet = _as_fleet(s)
    fl = s.pop("fl", None)
    if fl is None:
        fl = FLSpec(
            num_clients=s.pop("num_clients"),
            num_clusters=s.pop("num_clusters"),
            tau1=s.pop("tau1", 2),
            tau2=s.pop("tau2", 1),
            alpha=s.pop("alpha", 2),
            learning_rate=s.pop("learning_rate", 0.01),
            impl=s.pop("impl", "dense"),
            topology=s.pop("topology", "ring"),
        )
    return RoundScheduler(
        fl, optimizer=s.pop("optimizer", None), latency=s.pop("latency", None),
        backend=s.pop("backend", None),
        rounds_per_step=s.pop("rounds_per_step", 1),
        prefetch=s.pop("prefetch", True), fleet=fleet,
        mesh=s.pop("mesh", None),
    )


@register_scheduler("async")
def _make_async(s: dict) -> AsyncScheduler:
    from .async_engine import AsyncConfig, make_speeds
    from .staleness import psi_constant, psi_exponential, psi_inverse

    clusters = _as_clusters(s)
    topology = _as_topology(s.pop("topology", "ring"), clusters.num_clusters)
    fleet = _as_fleet(s)
    profile = fleet.resolve_profile(clusters.num_clients)
    speeds = s.pop("speeds", None)
    if speeds is None and profile is None:
        speeds = make_speeds(
            clusters.num_clients,
            s.pop("heterogeneity", 1.0),
            seed=s.pop("speed_seed", 0),
        )
    psi = s.pop("psi", psi_inverse)
    if isinstance(psi, str):
        psi = {
            "staleness": psi_inverse,
            "constant": psi_constant,
            "exponential": psi_exponential(),
        }[psi]
    cfg = AsyncConfig(
        clusters=clusters,
        topology=topology,
        speeds=None if speeds is None else np.asarray(speeds),
        learning_rate=s.pop("learning_rate", 0.01),
        theta_min=s.pop("theta_min", 1),
        theta_max=s.pop("theta_max", 20),
        min_batches=s.pop("min_batches", 4),
        psi=psi,
        alpha_latency=s.pop("latency", None),
        profile=profile,
    )
    return AsyncScheduler(
        cfg, backend=s.pop("backend", None), prefetch=s.pop("prefetch", True),
        fleet=fleet,
    )


def make_run(scenario) -> FederationRuntime:
    """Build a ``FederationRuntime`` from a run configuration.

    Accepts, in order of preference:

    * a typed :class:`repro.core.config.RunConfig` (validated, one schema
      shared with scenarios, ``launch/train.py`` and checkpoints);
    * a scenario *name* (``make_run("straggler-bimodal-async")``) or a dict
      with a ``"scenario"`` key whose remaining entries override the
      registered config — resolved via ``repro.scenarios``;
    * a legacy flat config dict — still works, but emits a
      ``DeprecationWarning`` and round-trips through
      ``RunConfig.from_dict`` / ``to_dict`` so it is validated by the same
      machinery as the typed path.

    Unconsumed keys raise, so typos fail fast.
    """
    if isinstance(scenario, RunConfig):
        rc = scenario
    else:
        if isinstance(scenario, str):
            scenario = {"scenario": scenario}
        s = dict(scenario)
        named = s.pop("scenario", None)
        if named is not None:
            from ..scenarios import get_scenario

            s = get_scenario(named).config(**s)
        else:
            warnings.warn(
                "make_run(<flat dict>) is deprecated; pass a "
                "repro.core.config.RunConfig (this dict was lifted through "
                "RunConfig.from_dict and validated on the same path)",
                DeprecationWarning,
                stacklevel=2,
            )
        rc = RunConfig.from_dict(s)
    rc.validate()
    s = rc.scheduler_config()
    name = s.pop("scheduler", "sync")
    s.pop("model", None)
    model = rc.model.build()
    seed = s.pop("seed", 0)
    sched = SCHEDULER_REGISTRY[name](s)
    if s:
        raise TypeError(f"unused scenario keys for {name!r}: {sorted(s)}")
    return FederationRuntime(model, sched, seed=seed)
