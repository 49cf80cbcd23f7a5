"""Pluggable aggregation backends: one Lemma-1 transition, three fast paths.

Every training regime in this repo ultimately applies the same linear
operator — the Lemma-1 transition ``W <- W @ T_k`` with
``T_k in {I, V B, V P^alpha B}`` — to a client-stacked pytree.  Before this
module each scheduler hard-wired its own implementation (dense einsum in
``SyncScheduler``/``round_engine``, ad-hoc Pallas routing in
``SyncScheduler``, shard_map collectives locked inside
``build_fl_train_step``).  ``AggregationBackend`` is the one interface over
all of them; schedulers receive a backend instance and never touch
aggregation code again.

The interface (``C`` clients, ``D`` clusters)::

    intra_cluster(stacked, weights)  (C, ...) -> (D, ...)   eq. 2-3 reduce
    inter_cluster(y, p, alpha)       (D, ...) -> (D, ...)   eq. 4 / eq. 21-22 mixing
    transition(stacked, event,       (C, ...) -> (C, ...)   full Lemma-1 T_k
               weights=None)

``transition``'s optional ``weights`` is a *traced* per-call (C,) vector of
intra-cluster client weights — the participation axis: a
``ParticipationPlan`` masks and renormalizes ``m^`` per round and threads
the result through here, so changing who participates changes array
*values*, never the compiled program.  ``weights=None`` uses the weights
bound at construction (the full-participation fast path, bit-identical to
the pre-participation code).

``transition``'s optional ``p`` is the same trick on the *topology* axis: a
traced per-call (D, D) mixing matrix that replaces the one bound at
construction for this call's inter-cluster stage (``repro.faults`` compiles
each round's surviving edge set into exactly this operand), so link
failures, ring→line rewires and server outages change values — never the
compiled program.  ``p=None`` keeps the statically-bound matrix and is
bitwise the pre-fault code path; ``p`` is ignored for ``intra``/``local``
events (they do not mix across clusters).

Registered implementations:

=================  ==========================================================
``DenseBackend``   Paper-faithful einsum against the precomputed ``T_k``
                   (and per-call mixing matrices for ``inter_cluster`` — the
                   path the async staleness mixing ``P_t`` takes).  Works for
                   any ``ClusterSpec``/topology; the reference for all
                   equivalence tests.
``PallasBackend``  Routes ``intra_cluster``/``inter_cluster`` through the
                   ``cluster_agg``/``gossip_mix`` TPU kernels and applies
                   ``transition`` with the fused ``V P^alpha B`` kernel, so
                   the (D, M) cluster intermediate never touches HBM.
                   Requires contiguous uniform clusters (C % D == 0).
``CollectiveBackend``  The structured shard_map path: weighted hypercube
                   all-reduce (log2(g) ppermutes) + alpha ring-ppermute
                   gossip rounds.  With a device mesh it runs as real ICI
                   collectives; without one it runs the *same* collective
                   code under ``vmap(axis_name=...)`` emulation, so it is
                   usable (and testable) from any scheduler, not just the
                   SPMD per-iteration step.  Requires a ring mixing stencil,
                   contiguous uniform clusters of power-of-two size, D >= 3.
=================  ==========================================================

``resolve_backend("auto", ...)`` picks by device mesh and cluster-size
divisibility: collective when a mesh spans the client axis and the collective
constraints hold, pallas on TPU with divisible clusters, dense otherwise
(including the non-power-of-two-cluster fallback).

New backends plug in via ``register_backend`` and become selectable from
``make_run({..., "backend": "<name>"})`` without touching any scheduler.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from .aggregation import (
    apply_transition_dense,
    dense_gossip_reference,
    hypercube_cluster_allreduce,
    ring_gossip,
    ring_mixing_weights,
)
from .protocol import AggregationEvent, ClusterSpec

PyTree = Any

__all__ = [
    "AggregationBackend",
    "DenseBackend",
    "PallasBackend",
    "CollectiveBackend",
    "BACKEND_REGISTRY",
    "register_backend",
    "resolve_backend",
    "select_auto_backend",
    "collective_supported",
]


@runtime_checkable
class AggregationBackend(Protocol):
    """One implementation of the Lemma-1 transition and its two factors."""

    name: str

    def intra_cluster(self, stacked: PyTree, weights: jax.Array) -> PyTree: ...

    def inter_cluster(self, y: PyTree, p: jax.Array, alpha: int) -> PyTree: ...

    def transition(
        self, stacked: PyTree, event: AggregationEvent,
        weights: Optional[jax.Array] = None,
        p: Optional[jax.Array] = None,
    ) -> PyTree: ...


def _uniform_contiguous(clusters: ClusterSpec) -> bool:
    """Clusters are contiguous, equally-sized blocks (the tiled-kernel layout)."""
    c, d = clusters.num_clients, clusters.num_clusters
    if c % d:
        return False
    g = c // d
    return clusters.assignments == tuple(i // g for i in range(c))


def _require_uniform_contiguous(clusters: ClusterSpec, backend: str) -> int:
    if not _uniform_contiguous(clusters):
        raise ValueError(
            f"{backend} backend requires contiguous uniform clusters "
            f"(C % D == 0, client i in cluster i // (C/D)); got "
            f"assignments={clusters.assignments}"
        )
    return clusters.num_clients // clusters.num_clusters


# ---------------------------------------------------------------------------
# Dense (paper-faithful) backend
# ---------------------------------------------------------------------------

class DenseBackend:
    """Lemma-1 einsums — correct everywhere, collective-hungry under pjit."""

    name = "dense"

    def __init__(self, clusters: ClusterSpec, p: np.ndarray, alpha: int, **_):
        self.clusters = clusters
        self.alpha = alpha
        self._t = {
            "intra": jnp.asarray(_t_matrix(clusters, p, alpha, "intra"), jnp.float32),
            "inter": jnp.asarray(_t_matrix(clusters, p, alpha, "inter"), jnp.float32),
        }
        # B indicator (C, D) for weight-parametrized intra reduce
        self._b_ind = jnp.asarray(clusters.B().T, jnp.float32)
        # right factors of the weighted transition T(w) = V(w) @ M_event:
        # M_intra = B, M_inter = P^alpha B (tiny (D, C), f64 on the host)
        b = clusters.B()
        p_a = np.linalg.matrix_power(np.asarray(p, np.float64), alpha)
        self._m_event = {
            "intra": jnp.asarray(b, jnp.float32),
            "inter": jnp.asarray(p_a @ b, jnp.float32),
        }

        @jax.jit
        def _intra(stacked, weights):
            v = self._b_ind * weights.astype(jnp.float32)[:, None]   # (C, D)
            return jax.tree.map(
                lambda w: jnp.einsum(
                    "c...,cd->d...", w.astype(jnp.float32), v
                ).astype(w.dtype),
                stacked,
            )

        self._intra = _intra

        @jax.jit
        def _apply_weighted(stacked, weights, m_event):
            # T(w)[i, j] = w_i * M_event[d(i), j]: the (C, D) one-hot rows of
            # B^T make the (C, D) @ (D, C) product exact per entry, so a full
            # mask (w == m^) reproduces the static T bit-for-bit
            v = self._b_ind * weights.astype(jnp.float32)[:, None]   # (C, D)
            return apply_transition_dense(stacked, v @ m_event)

        self._apply_weighted = _apply_weighted

        # per-call mixing matrices (the fault/churn axis): P_r enters as a
        # traced operand, P_r^alpha and the (D, C) right factor are computed
        # on device — same einsum shape as the static path, so churn never
        # recompiles.  alpha is static (closure), matrix_power unrolls.
        m_hat_full = jnp.asarray(clusters.m_hat(), jnp.float32)

        @jax.jit
        def _apply_inter_p(stacked, weights, p_call):
            p_a = jnp.linalg.matrix_power(p_call.astype(jnp.float32), alpha)
            m_event = p_a @ self._m_event["intra"]          # P_r^a @ B: (D, C)
            v = self._b_ind * weights.astype(jnp.float32)[:, None]
            return apply_transition_dense(stacked, v @ m_event)

        self._apply_inter_p = _apply_inter_p
        self._m_hat_full = m_hat_full

        # matrix_power on the tiny (D, D) P, then ONE tree sweep — not alpha
        # full HBM passes over the model
        self._inter = jax.jit(
            dense_gossip_reference, static_argnames=("alpha",)
        )
        self._apply = jax.jit(apply_transition_dense)

    def intra_cluster(self, stacked: PyTree, weights: jax.Array) -> PyTree:
        return self._intra(stacked, weights)

    def inter_cluster(self, y: PyTree, p: jax.Array, alpha: int = 1) -> PyTree:
        return self._inter(y, jnp.asarray(p), alpha=alpha)

    def transition(self, stacked: PyTree, event: AggregationEvent,
                   weights: Optional[jax.Array] = None,
                   p: Optional[jax.Array] = None) -> PyTree:
        if event == "local":
            return stacked
        if p is not None and event == "inter":
            w = self._m_hat_full if weights is None else weights
            return self._apply_inter_p(stacked, w, jnp.asarray(p, jnp.float32))
        if weights is None:
            return self._apply(stacked, self._t[event])
        return self._apply_weighted(stacked, weights, self._m_event[event])


def _t_matrix(clusters: ClusterSpec, p: np.ndarray, alpha: int,
              event: AggregationEvent) -> np.ndarray:
    """Lemma-1 T_k from raw factors (protocol.transition_matrix needs a config)."""
    v, b = clusters.V(), clusters.B()
    if event == "intra":
        return v @ b
    return v @ np.linalg.matrix_power(np.asarray(p, np.float64), alpha) @ b


# ---------------------------------------------------------------------------
# Pallas kernel backend
# ---------------------------------------------------------------------------

class PallasBackend:
    """Tiled TPU kernels; fused V P^alpha B for the full transition.

    ``interpret`` defaults to True off-TPU so the same code path is testable
    on CPU runners.  ``tile_m`` caps the lanes of a kernel block (a multiple
    of 128); the kernels size the rest of a block from its VMEM budget.
    """

    name = "pallas"

    def __init__(self, clusters: ClusterSpec, p: np.ndarray, alpha: int,
                 interpret: Optional[bool] = None, tile_m: int = 512, **_):
        self.clusters = clusters
        self.alpha = alpha
        self.interpret = (
            jax.default_backend() != "tpu" if interpret is None else interpret
        )
        self.tile_m = tile_m
        self._vt = jnp.asarray(clusters.V().T, jnp.float32)   # (D, C)
        self._bt = jnp.asarray(clusters.B().T, jnp.float32)   # (C, D)
        self._p = jnp.asarray(p, jnp.float32)

    def intra_cluster(self, stacked: PyTree, weights: jax.Array) -> PyTree:
        from repro.kernels import cluster_agg_tree

        # the kernel reduces client k*g+j into cluster k: contiguous, uniform
        _require_uniform_contiguous(self.clusters, "pallas")
        return cluster_agg_tree(
            stacked, jnp.asarray(weights, jnp.float32),
            self.clusters.num_clusters,
            interpret=self.interpret, tile_m=self.tile_m,
        )

    def inter_cluster(self, y: PyTree, p: jax.Array, alpha: int = 1) -> PyTree:
        from repro.kernels import gossip_mix_tree

        return gossip_mix_tree(
            y, jnp.asarray(p, jnp.float32), alpha=alpha,
            interpret=self.interpret, tile_m=self.tile_m,
        )

    def transition(self, stacked: PyTree, event: AggregationEvent,
                   weights: Optional[jax.Array] = None,
                   p: Optional[jax.Array] = None) -> PyTree:
        from repro.kernels import fused_transition_tree

        if event == "local":
            return stacked
        # alpha=0 skips the mixing stage: V B.  The (D, ...) intermediate
        # stays in VMEM either way.
        alpha = self.alpha if event == "inter" else 0
        if weights is None:
            vt = self._vt
        else:
            # V(w)^T: the per-round weights replace m^ in the upload factor;
            # bt.T is the exact 0/1 indicator, so vt rows carry w verbatim
            # and the same fused kernel serves every participation draw
            vt = self._bt.T * weights.astype(jnp.float32)[None, :]
        # the fused kernel's P is already a traced operand — a per-round
        # faulted mixing matrix substitutes values into the same program
        p_call = self._p if p is None or event != "inter" else jnp.asarray(
            p, jnp.float32
        )
        return fused_transition_tree(
            stacked, vt, p_call, self._bt, alpha=alpha,
            interpret=self.interpret, tile_m=self.tile_m,
        )


# ---------------------------------------------------------------------------
# Structured collective backend (shard_map on a mesh, vmap emulation off it)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("axis_name", "axis_size", "cluster_size", "alpha", "event"),
)
def _vmapped_transition(tree, m_hat, wl, ws, wr, *, axis_name, axis_size,
                        cluster_size, alpha, event):
    def per_client(x, w, l, s, r):
        y = hypercube_cluster_allreduce(x, axis_name, axis_size, cluster_size, w)
        if event == "inter":
            y = ring_gossip(y, axis_name, axis_size, cluster_size, l, s, r, alpha)
        return y.astype(x.dtype)

    vm = jax.vmap(per_client, in_axes=(0, 0, None, None, None), axis_name=axis_name)
    return jax.tree.map(lambda leaf: vm(leaf, m_hat, wl, ws, wr), tree)


@functools.partial(
    jax.jit, static_argnames=("axis_name", "axis_size", "alpha")
)
def _vmapped_gossip(tree, wl, ws, wr, *, axis_name, axis_size, alpha):
    def per_cluster(x, l, s, r):
        return ring_gossip(x, axis_name, axis_size, 1, l, s, r, alpha).astype(x.dtype)

    vm = jax.vmap(per_cluster, in_axes=(0, None, None, None), axis_name=axis_name)
    return jax.tree.map(lambda leaf: vm(leaf, wl, ws, wr), tree)


class CollectiveBackend:
    """Hypercube all-reduce + ring ppermute gossip over the client axis.

    With ``mesh``/``param_specs`` the transition runs under ``shard_map`` as
    real collectives (one client per ``axis_name`` mesh index, bytes
    proportional to one model instead of C).  Without a mesh the identical
    per-device function runs under ``vmap`` with the same ``axis_name`` —
    JAX lowers the ppermutes to gathers, so every scheduler (and every CPU
    test) exercises the collective code path.
    """

    name = "collective"

    def __init__(self, clusters: ClusterSpec, p: np.ndarray, alpha: int,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 param_specs: Optional[PyTree] = None,
                 axis_name: Optional[str] = None, **_):
        g = _require_uniform_contiguous(clusters, "collective")
        if g & (g - 1):
            raise ValueError(
                f"collective backend requires power-of-two cluster sizes for the "
                f"hypercube all-reduce; got cluster_size={g}"
            )
        d = clusters.num_clusters
        if d < 3:
            raise ValueError("collective ring gossip needs >= 3 clusters")
        self.clusters = clusters
        self.cluster_size = g
        self.alpha = alpha
        self.mesh = mesh
        self.param_specs = param_specs
        self.axis_name = axis_name or ("data" if mesh is not None else "clients")
        # raises if P has support off the ring stencil (non-ring topology)
        w_l, w_s, w_r = ring_mixing_weights(np.asarray(p, np.float64))
        self._ring_w = tuple(jnp.asarray(w, jnp.float32) for w in (w_l, w_s, w_r))
        self._m_hat = jnp.asarray(clusters.m_hat(), jnp.float32)

    def _ring_stencil(self, p: jax.Array) -> tuple:
        """Per-cluster (w_left, w_self, w_right) gathered from a traced P.

        The device-side twin of ``ring_mixing_weights``: column ``d`` of a
        ring-stencil matrix holds exactly the three weights cluster ``d``'s
        gossip update uses, so a per-round faulted matrix (downed ring links
        zero their entries; the component renormalization moves the mass to
        ``w_self``) becomes three traced (D,) vectors and the same ppermute
        program.  Support off the ring stencil cannot be checked on traced
        values — ``FaultSchedule.mixing_stack(require_ring_stencil=True)``
        validates host-side before the stack is shipped.
        """
        d = self.clusters.num_clusters
        idx = jnp.arange(d)
        p = jnp.asarray(p, jnp.float32)
        return p[(idx - 1) % d, idx], p[idx, idx], p[(idx + 1) % d, idx]

    # -- full Lemma-1 transition, (C, ...) -> (C, ...) -----------------------
    def transition(self, stacked: PyTree, event: AggregationEvent,
                   weights: Optional[jax.Array] = None,
                   p: Optional[jax.Array] = None) -> PyTree:
        if event == "local":
            return stacked
        if p is None or event != "inter":
            wl, ws, wr = self._ring_w
        else:
            wl, ws, wr = self._ring_stencil(p)
        c = self.clusters.num_clients
        # the per-client weight is already a traced operand of the weighted
        # all-reduce; participation just substitutes the round's vector
        m_hat = self._m_hat if weights is None else jnp.asarray(
            weights, jnp.float32
        )
        if self.mesh is not None:
            if p is not None and event == "inter":
                return self._shard_map_transition_p(stacked, event, m_hat,
                                                    (wl, ws, wr))
            return self._shard_map_transition(stacked, event, m_hat)
        return _vmapped_transition(
            stacked, m_hat, wl, ws, wr,
            axis_name=self.axis_name, axis_size=c,
            cluster_size=self.cluster_size, alpha=self.alpha, event=event,
        )

    def _shard_map_transition(self, stacked: PyTree, event: AggregationEvent,
                              m_hat: jax.Array) -> PyTree:
        specs = self.param_specs
        if specs is None:
            # default layout: every stacked leaf is sharded on its leading
            # clients axis, replicated elsewhere — exactly the layout the
            # batched local-update stage pins via its sharding constraint
            specs = jax.tree.map(
                lambda _: jax.sharding.PartitionSpec(self.axis_name), stacked
            )
        wl, ws, wr = self._ring_w
        c, g, alpha = self.clusters.num_clients, self.cluster_size, self.alpha
        axis = self.axis_name
        w_spec = jax.sharding.PartitionSpec(axis)

        def agg(tree, m_hat_shard):
            w = m_hat_shard.reshape(())  # (1,) shard -> scalar

            def per_leaf(x):
                y = hypercube_cluster_allreduce(x, axis, c, g, w)
                if event == "inter":
                    y = ring_gossip(y, axis, c, g, wl, ws, wr, alpha)
                return y.astype(x.dtype)

            return jax.tree.map(per_leaf, tree)

        return jax.shard_map(
            agg, mesh=self.mesh,
            in_specs=(specs, w_spec), out_specs=specs, check_vma=False,
        )(stacked, m_hat)

    def _shard_map_transition_p(self, stacked: PyTree, event: AggregationEvent,
                                m_hat: jax.Array, ring_w: tuple) -> PyTree:
        """Mesh transition with *traced* ring weights (the fault/churn path).

        A sibling of ``_shard_map_transition`` rather than a parameter of it:
        the fault-free method closes over the statically-bound stencil and
        stays bitwise-identical to pre-fault code, while this one threads the
        per-round (D,) vectors through as replicated shard_map operands.
        """
        specs = self.param_specs
        if specs is None:
            specs = jax.tree.map(
                lambda _: jax.sharding.PartitionSpec(self.axis_name), stacked
            )
        c, g, alpha = self.clusters.num_clients, self.cluster_size, self.alpha
        axis = self.axis_name
        w_spec = jax.sharding.PartitionSpec(axis)
        rep = jax.sharding.PartitionSpec()

        def agg(tree, m_hat_shard, wl, ws, wr):
            w = m_hat_shard.reshape(())  # (1,) shard -> scalar

            def per_leaf(x):
                y = hypercube_cluster_allreduce(x, axis, c, g, w)
                if event == "inter":
                    y = ring_gossip(y, axis, c, g, wl, ws, wr, alpha)
                return y.astype(x.dtype)

            return jax.tree.map(per_leaf, tree)

        wl, ws, wr = ring_w
        return jax.shard_map(
            agg, mesh=self.mesh,
            in_specs=(specs, w_spec, rep, rep, rep), out_specs=specs,
            check_vma=False,
        )(stacked, m_hat, wl, ws, wr)

    # -- factors -------------------------------------------------------------
    def intra_cluster(self, stacked: PyTree, weights: jax.Array) -> PyTree:
        c, g = self.clusters.num_clients, self.cluster_size
        wl, ws, wr = self._ring_w
        reduced = _vmapped_transition(
            stacked, jnp.asarray(weights, jnp.float32), wl, ws, wr,
            axis_name=self.axis_name, axis_size=c,
            cluster_size=g, alpha=self.alpha, event="intra",
        )
        # every member of a cluster holds the reduced model; take the leads
        return jax.tree.map(lambda leaf: leaf[::g], reduced)

    def inter_cluster(self, y: PyTree, p: jax.Array, alpha: int = 1) -> PyTree:
        # P may change per call (async staleness mixing P_t) — re-derive the
        # ring stencil weights on the host; raises off-ring.
        wl, ws, wr = (
            jnp.asarray(w, jnp.float32)
            for w in ring_mixing_weights(np.asarray(p, np.float64))
        )
        return _vmapped_gossip(
            y, wl, ws, wr, axis_name=self.axis_name,
            axis_size=self.clusters.num_clusters, alpha=alpha,
        )


# ---------------------------------------------------------------------------
# Registry + auto selection
# ---------------------------------------------------------------------------

BACKEND_REGISTRY: dict[str, Callable[..., AggregationBackend]] = {}


def register_backend(name: str):
    """Register a backend factory ``(clusters, p, alpha, **kw) -> backend``."""

    def deco(factory: Callable[..., AggregationBackend]):
        BACKEND_REGISTRY[name] = factory
        return factory

    return deco


register_backend("dense")(DenseBackend)
register_backend("pallas")(PallasBackend)
register_backend("collective")(CollectiveBackend)


def collective_supported(clusters: ClusterSpec, p: np.ndarray) -> bool:
    """Can CollectiveBackend represent this scenario?  (See class docstring.)"""
    if not _uniform_contiguous(clusters) or clusters.num_clusters < 3:
        return False
    g = clusters.num_clients // clusters.num_clusters
    if g & (g - 1):  # hypercube needs power-of-two cluster sizes
        return False
    try:
        ring_mixing_weights(np.asarray(p, np.float64))
    except ValueError:
        return False
    return True


def select_auto_backend(clusters: ClusterSpec, p: np.ndarray,
                        mesh: Optional[jax.sharding.Mesh] = None,
                        axis_name: str = "data") -> str:
    """Pick a backend name by device mesh and cluster-size divisibility.

    * ``collective`` when a mesh axis spans the client axis one-to-one and
      the scenario satisfies the collective constraints (ring stencil,
      power-of-two uniform clusters) — the ICI-native path;
    * ``pallas`` on TPU with contiguous uniform clusters (C % D == 0), where
      the fused kernels beat the XLA einsum;
    * ``dense`` everywhere else — including non-power-of-two or ragged
      clusters, and CPU hosts where interpret-mode kernels would only slow
      the einsum down.
    """
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes.get(axis_name) == clusters.num_clients and collective_supported(
            clusters, p
        ):
            return "collective"
    if jax.default_backend() == "tpu" and _uniform_contiguous(clusters):
        return "pallas"
    return "dense"


def resolve_backend(spec, clusters: ClusterSpec, p: np.ndarray, alpha: int,
                    **kwargs) -> AggregationBackend:
    """Turn a backend spec into a bound instance.

    ``spec`` is a registered name, ``"auto"``, ``None`` (== auto), or an
    already-constructed backend (returned as-is).  ``kwargs`` are forwarded
    to the factory (``mesh``, ``param_specs``, ``interpret``, ``tile_m``...).
    """
    if spec is None:
        spec = "auto"
    if not isinstance(spec, str):
        return spec  # pre-built backend instance
    name = spec
    if name == "auto":
        name = select_auto_backend(
            clusters, p, mesh=kwargs.get("mesh"),
            axis_name=kwargs.get("axis_name") or "data",
        )
    if name not in BACKEND_REGISTRY:
        raise KeyError(
            f"unknown aggregation backend {name!r}; registered: "
            f"{sorted(BACKEND_REGISTRY)}"
        )
    return BACKEND_REGISTRY[name](clusters, np.asarray(p, np.float64), alpha, **kwargs)
