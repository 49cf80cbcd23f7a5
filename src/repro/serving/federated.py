"""Per-cluster personalized serving: one engine, D model replicas, hot swap.

In SD-FEEL the per-cluster models genuinely differ between inter-cluster
aggregations — that divergence is the point of the intra/inter aggregation
split — so serving every request from the consensus model throws away the
personalization the protocol just paid for.  Two servers front the ``D``
per-cluster replicas, which live as ONE stacked ``(D, ...)`` parameter tree
(the same stacked-tree layout the round engine trains):

* :class:`FederatedServer` — the static-drain engine.  Requests bucket by
  ``(cluster, padded_len)`` so a batch never mixes clusters; the jitted
  prefill/decode programs take the *cluster index as a traced operand*, so
  one compiled program per bucket shape serves every cluster.

* :class:`ContinuousFederatedServer` — the slot-pool engine.  Every slot
  carries its own traced cluster index: slots from *different* clusters
  decode side by side in one program (each slot gathers its cluster's tree
  inside the vmap), so the Zipf tail no longer fragments batches.  With
  ``mesh=`` the stacked ``(D, ...)`` replica axis is sharded across the
  cluster mesh from ``launch/mesh.py`` via ``repro.sharding`` specs —
  serving and training share one mesh — with the gather/vmap path as the
  off-mesh fallback (bitwise-identical outputs).

Weights hot-swap from a live :class:`~repro.core.runtime.FederationRuntime`
through a double-buffered device slot (:class:`ReplicaBuffer`): ``publish``
stages the new stack into the inactive slot (the transfer overlaps
in-flight decode) and the server flips atomically at a weight boundary.
For the static engine that boundary is the next batch; for the continuous
engine it is the next *slot-admission boundary with an empty pool*: a
pending publish closes admission, in-flight slots drain on the weights they
prefilled with (their KV cache survives the swap untouched), the flip
happens once the pool is empty, and admission reopens on the new weights —
new requests use new weights, in-flight requests finish on the old ones,
asserted bitwise at fp32 in the tests.

``serving/traffic.py`` generates the synthetic per-cluster request mix the
benchmarks replay against these servers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .engine import BatchServer, ContinuousServer, Request, _bucket_len

__all__ = ["FederatedServer", "ContinuousFederatedServer", "ReplicaBuffer"]


def _copy_tree(tree):
    """Own the buffers: schedulers donate their stacks on the next step."""
    return jax.tree.map(lambda x: jnp.asarray(x).copy(), tree)


class ReplicaBuffer:
    """Double-buffered stacked ``(D, ...)`` replica tree with validation.

    ``stage`` copies (and optionally mesh-places) a published stack into the
    inactive slot — rejecting cluster-count mismatches and non-finite
    leaves before they can displace last-good weights — and ``flip`` makes
    it active.  When and whether to flip is the *server's* policy (batch
    boundary vs drained slot pool); the buffer only guarantees a reader
    never observes a half-written tree.
    """

    def __init__(self, stack, *, place=None):
        self._place = place or (lambda t: t)
        self._slots = [self._place(_copy_tree(stack)), None]
        self._active = 0
        self.pending = False
        self.num_clusters = int(jax.tree.leaves(stack)[0].shape[0])
        self.swaps = 0

    @property
    def active_stack(self):
        return self._slots[self._active]

    def stage(self, stack) -> None:
        stack = _copy_tree(stack)
        d = int(jax.tree.leaves(stack)[0].shape[0])
        if d != self.num_clusters:
            raise ValueError(
                f"published stack has {d} clusters, server has {self.num_clusters}"
            )
        for path, leaf in jax.tree_util.tree_leaves_with_path(stack):
            if not bool(jnp.all(jnp.isfinite(leaf))):
                raise ValueError(
                    f"published stack has non-finite values at "
                    f"{jax.tree_util.keystr(path)}; keeping last-good weights"
                )
        self._slots[1 - self._active] = self._place(stack)
        self.pending = True

    def flip(self) -> bool:
        if not self.pending:
            return False
        self._active = 1 - self._active
        self._slots[1 - self._active] = None
        self.pending = False
        self.swaps += 1
        return True


class _FederatedMixin:
    """Shared publish/sync/routing surface over a :class:`ReplicaBuffer`."""

    _buf: ReplicaBuffer
    _runtime = None
    rejected = 0

    @property
    def num_clusters(self) -> int:
        return self._buf.num_clusters

    @property
    def swaps(self) -> int:
        return self._buf.swaps

    @property
    def active_params(self):
        """The stacked tree decode is currently running against."""
        return self._buf.active_stack

    def publish(self, cluster_params) -> None:
        """Stage a new stacked tree; it becomes active at the next weight
        boundary (batch for the static engine, drained slot pool for the
        continuous one).

        The copy/transfer happens now (overlapping any in-flight decode
        dispatches); only the flip waits for the boundary, so in-flight
        work keeps bit-stable weights end to end.  A stack carrying
        non-finite leaves is rejected with ``ValueError`` before it touches
        the inactive slot — a training source that died mid-round (fault
        injection, NaN blow-up) can never displace the last-good serving
        weights.
        """
        self._buf.stage(cluster_params)

    def sync_from(self, runtime=None) -> bool:
        """Publish the attached (or given) runtime's current cluster models.

        Returns ``True`` on success.  If the source dies mid-swap — raises
        while materializing its stack, or hands over a non-finite/misshapen
        one — the staged slot is left untouched, the server keeps serving
        its last-good double-buffered weights, ``rejected`` is incremented,
        and ``False`` is returned.  A missing runtime is still a
        ``ValueError``: that is a wiring bug, not a fault.  So is an error
        of the device itself (``jax.errors.JaxRuntimeError``, e.g. out of
        memory): it propagates, since the server could not serve either.
        """
        rt = runtime or self._runtime
        if rt is None:
            raise ValueError("no runtime attached; pass one or construct with runtime=")
        try:
            self.publish(rt.cluster_params())
        except jax.errors.JaxRuntimeError:
            raise
        except Exception:
            self.rejected += 1
            return False
        return True

    def _check_cluster(self, req: Request) -> None:
        if req.cluster_id is None:
            raise ValueError("federated serving requests must carry a cluster_id")
        if not 0 <= req.cluster_id < self.num_clusters:
            raise ValueError(
                f"cluster_id {req.cluster_id} out of range [0, {self.num_clusters})"
            )

    @staticmethod
    def _resolve_stack(cluster_params, runtime):
        if cluster_params is None:
            if runtime is None:
                raise ValueError("need cluster_params or a runtime to pull them from")
            cluster_params = runtime.cluster_params()
        return cluster_params


class FederatedServer(_FederatedMixin, BatchServer):
    """Static-drain serving over stacked per-cluster model replicas.

    ``cluster_params`` is a pytree whose leaves carry a leading ``(D, ...)``
    cluster axis (``FederationRuntime.cluster_params()`` returns exactly
    this).  Alternatively pass ``runtime=`` and the initial stack is pulled
    from it; ``sync_from()`` then republishes at round boundaries.
    """

    def __init__(
        self,
        model,
        cluster_params=None,
        *,
        runtime=None,
        max_batch: int = 8,
        length_buckets: tuple[int, ...] = (32, 64, 128),
        temperature: float = 0.0,
        seed: int = 0,
        cache_len=None,
        reorder_window=None,
        max_head_skips: int = 4,
    ):
        cluster_params = self._resolve_stack(cluster_params, runtime)
        super().__init__(
            model, None, max_batch=max_batch, length_buckets=length_buckets,
            temperature=temperature, seed=seed, cache_len=cache_len,
            reorder_window=reorder_window, max_head_skips=max_head_skips,
        )
        self._runtime = runtime
        self.rejected = 0
        self._buf = ReplicaBuffer(cluster_params)

        def fed_prefill(stacked, d, batch):
            p = jax.tree.map(lambda w: w[d], stacked)
            return model.prefill(p, batch)

        def fed_decode(stacked, d, tok, cache, pos):
            p = jax.tree.map(lambda w: w[d], stacked)
            return model.decode_step(p, tok, cache, pos)

        # d is traced: one compiled program per bucket shape serves all D
        # clusters (the gathered slice is a dynamic index into the stack)
        self._fed_prefill = jax.jit(fed_prefill)
        self._fed_decode = jax.jit(fed_decode)

    def _begin_batch(self, batch) -> None:
        self._buf.flip()

    # -- routing -------------------------------------------------------------
    def submit(self, req: Request):
        self._check_cluster(req)
        super().submit(req)

    def _batch_key(self, req: Request):
        return (req.cluster_id, _bucket_len(req.prompt.shape[-1], self.buckets))

    # -- model hooks ---------------------------------------------------------
    def _run_prefill(self, batch, toks):
        d = jnp.int32(batch[0].cluster_id)
        return self._fed_prefill(self._buf.active_stack, d, {"tokens": toks})

    def _run_decode(self, batch, tok, cache, pos):
        d = jnp.int32(batch[0].cluster_id)
        return self._fed_decode(self._buf.active_stack, d, tok, cache, pos)


class ContinuousFederatedServer(_FederatedMixin, ContinuousServer):
    """Continuous slot-pool serving over stacked per-cluster replicas.

    Slots are cluster-heterogeneous: each carries a traced cluster index and
    gathers its own replica inside the vmapped decode, so one compiled
    program serves any cluster mix the Zipf trace produces.  Hot-swap
    semantics differ from the static engine (see module docstring): a
    pending publish closes admission, in-flight slots drain on their
    prefill-time weights, and the buffer flips at the first admission
    boundary with an empty pool.

    ``mesh=`` shards the stacked ``(D, ...)`` replica axis across a cluster
    mesh (one replica per device row): pass a mesh whose ``axis_name`` axis
    has size ``D`` — ``launch.mesh.make_cluster_mesh`` builds one — or
    ``"auto"`` to use it iff enough devices exist.  Off-mesh the same
    programs run on replicated buffers; outputs are bitwise-identical.
    """

    _stacked = True

    def __init__(
        self,
        model,
        cluster_params=None,
        *,
        runtime=None,
        mesh=None,
        mesh_axis: str = "cluster",
        max_batch: int = 8,
        length_buckets: tuple[int, ...] = (32, 64, 128),
        gen_cap: int = 64,
        chunk_steps: int = 8,
        temperature: float = 0.0,
        seed: int = 0,
    ):
        from repro.launch.mesh import resolve_cluster_mesh
        from repro.sharding.rules import replica_pspecs

        cluster_params = self._resolve_stack(cluster_params, runtime)
        num_clusters = int(jax.tree.leaves(cluster_params)[0].shape[0])
        self.mesh = resolve_cluster_mesh(mesh, num_clusters, mesh_axis)
        if self.mesh is not None:
            specs = replica_pspecs(cluster_params, mesh_axis)
            place = lambda t: jax.tree.map(  # noqa: E731
                lambda x, s: jax.device_put(
                    x, jax.sharding.NamedSharding(self.mesh, s)),
                t, specs,
            )
        else:
            place = None
        super().__init__(
            model, None, max_batch=max_batch, length_buckets=length_buckets,
            gen_cap=gen_cap, chunk_steps=chunk_steps, temperature=temperature,
            seed=seed,
        )
        self._runtime = runtime
        self.rejected = 0
        self._buf = ReplicaBuffer(cluster_params, place=place)
        if self.mesh is not None:
            # the slot pool starts replicated on the mesh, the sharding the
            # programs hand it back with, so no admission recompiles
            self._state = jax.device_put(self._state, jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()))

    # -- weight hooks ---------------------------------------------------------
    def _weights(self):
        return self._buf.active_stack

    def _cluster_index(self, req: Request):
        return jnp.int32(req.cluster_id)

    def _admission_open(self) -> bool:
        # a pending publish closes admission: in-flight slots drain on the
        # weights they prefilled with, new requests wait for the flip
        return not self._buf.pending

    def _at_admission_boundary(self) -> None:
        if self._buf.pending and not self._occupied:
            self._buf.flip()

    def submit(self, req: Request):
        self._check_cluster(req)
        super().submit(req)
