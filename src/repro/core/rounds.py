"""The bound fleet: who is in round ``r``, with what weights, over what
mixing, at what price.

The sync, round and async schedulers advance the federation at different
grains (one protocol iteration, ``R`` scan-compiled rounds, one cluster
event) but share one rule per aggregation round ``r``: its clients are the
participation plan's mask AND the fault schedule's survivors; its weights
are that mask renormalized per cluster (a fully crashed cluster falls back
to its ``m^`` column), or the plan's weights sliced onto the resident slots
of a host-offload store; its mixing is the fault schedule's matrix; its
Section V-B price is paced by its clients along each cluster's critical
path and, under a trace profile, by row ``r``.  :class:`FleetRounds`
resolves the fleet once and answers the rule per round as a
:class:`RoundOperands` record.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class RoundOperands:
    """One round's traced operands and pacing; ``None`` where a path has none.

    ``weights`` is ``(C,)`` or, offloaded, ``(k_max,)`` float32; ``mixing``
    the ``(D, D)`` float32 matrix; ``pace`` the ``(C,)`` clients that pace
    the round; ``penalty`` the uplink retries, charged once at its inter
    event; ``t`` the trace row that prices it.  ``times`` caches the price
    of each event kind, so a record is priced once per kind.
    """

    t: Optional[int] = None
    weights: Optional[jax.Array] = None
    mixing: Optional[jax.Array] = None
    pace: Optional[np.ndarray] = None
    penalty: float = 0.0
    args: tuple = ()
    times: dict = dataclasses.field(default_factory=dict, compare=False)


class FleetRounds:
    """A scheduler's fleet, bound once.

    ``proto`` carries ``clusters`` and ``topology`` (and, for pricing,
    ``alpha``, ``tau1``, ``tau2`` and ``event_at``).  Pricing works from
    construction; :meth:`bind` resolves the seeded axes.
    """

    def __init__(self, fleet, proto, latency=None, profile=None):
        self.fleet, self.proto, self.latency, self.profile = fleet, proto, latency, profile
        self.timing = None
        if profile is not None:
            from ..hetero import FleetTiming

            self.timing = FleetTiming(profile, latency)
        self.schedule = None if profile is None else profile.schedule
        self.plan = self.faults = self.store = None
        self.sampling = False
        self.variant = "static"
        self.full = RoundOperands()  # the full fleet, static pricing
        self._last = None

    def bind(self, seed: int, rows: Optional[int] = None,
             weighted_offload: bool = False) -> None:
        """Resolve plan and faults, and the client-state store over ``rows``
        (the async scheduler, whose state is per cluster, keeps its own).

        The compiled variant is ``"mixing"`` (weights + mixing) under faults,
        ``"weights"`` under sampling, and ``"static"`` otherwise, where the
        backend's bound ``m^`` applies.  ``weighted_offload`` gives offloaded
        slots the weights variant even without sampling.
        """
        from ..faults import resolve_faults
        from ..participation import resolve_plan

        clusters = self.proto.clusters
        self.plan = resolve_plan(self.fleet.participation, clusters,
                                 profile=self.profile, seed=seed)
        # "full" keeps the static program: bit-identical to a plan-free run
        self.sampling = self.plan is not None and not self.plan.is_full
        # an empty schedule resolves to None: bitwise the fault-free path
        self.faults = resolve_faults(self.fleet.faults, self.proto.topology,
                                     clusters)
        resident = True
        if rows is not None:
            self.store = self.fleet.resolve_store(rows)
            resident = self.store.resident
            if self.faults is not None and not resident:
                raise ValueError(
                    "fault injection requires a resident client-state store; "
                    "host-offload runs cannot thread per-round fault operands"
                )
        self.variant = (
            "mixing" if self.faults is not None
            else "weights" if self.sampling or (weighted_offload and not resident)
            else "static"
        )
        self._per_round = (self.sampling or self.faults is not None
                           or self.schedule is not None)
        # a superstep sums its rounds' prices for a trace, resident sampling
        # and faults on a profiled fleet; elsewhere it takes R times one
        # round's price (the same number up to float rounding)
        self._sum_rounds = (self.schedule is not None
                            or (self.sampling and resident)
                            or (self.faults is not None and self.profile is not None))
        self._last = None

    # -- who -----------------------------------------------------------------
    def client_mask(self, r: int, fault_r: Optional[int] = None) -> np.ndarray:
        """Round ``r``'s plan mask AND round ``fault_r``'s (default ``r``)
        surviving clients."""
        mask = (self.plan.mask(r) if self.sampling
                else np.ones(self.proto.clusters.num_clients, dtype=bool))
        if self.faults is not None:
            mask = mask & self.faults.client_mask(r if fault_r is None else fault_r)
        return mask

    def residency(self, r: int):
        """Round ``r``'s slot assignment: deterministic in ``r``, so prefetch
        and execution agree."""
        return self.store.residency(self.plan.mask(r) if self.sampling else None)

    # -- with what weights, over what mixing ---------------------------------
    def operands(self, r: int, residency=None, draw: Optional[int] = None) -> RoundOperands:
        """Round ``r``'s record, cached for the last round asked.

        Offloaded (``residency`` given), the weights are the plan's for the
        round ``draw`` (default ``r``) that placed the residents, and the
        residents pace the round.
        """
        key = (r, draw) if self._per_round else None
        if self._last is not None and self._last[0] == key:
            return self._last[1]
        from ..participation import renormalize_weights
        from ..state import sub_weights

        clusters = self.proto.clusters
        weights = mixing = pace = None
        penalty = 0.0
        if residency is not None:
            pace = residency.participant_mask(clusters.num_clients)
            if self.variant != "static":
                full = (self.plan.weights(r if draw is None else draw)
                        if self.sampling else clusters.m_hat())
                weights = sub_weights(full, residency)
        elif self.faults is not None:
            pace = self.client_mask(r)
            weights = renormalize_weights(clusters.m_hat(), clusters.assignments, pace)
            mixing = jnp.asarray(self.faults.mixing_at(r), jnp.float32)
            if self.timing is not None:
                penalty = self.timing.uplink_retry_penalty(self.faults.uplink_failed(r))
        elif self.sampling:
            weights = self.plan.weights(r)
            pace = self.plan.effective_mask(r)
        if weights is not None:
            weights = jnp.asarray(weights, jnp.float32)
        args = {"static": (), "weights": (weights,),
                "mixing": (weights, mixing)}[self.variant]
        ops = RoundOperands(t=r if self.schedule is not None else None,
                            weights=weights, mixing=mixing, pace=pace,
                            penalty=penalty, args=args)
        self._last = (key, ops)
        return ops

    # -- at what price -------------------------------------------------------
    def event_time(self, event: str, ops: Optional[RoundOperands] = None) -> float:
        """Section V-B wall-clock of one ``event`` iteration in ``ops``'s
        round (the full fleet's by default).

        With a profile, the round's ``pace`` clients set it along each
        cluster's critical path (``FleetTiming.sync_event_time``); without
        one, the latency model's per-event constants do.
        """
        if ops is None or self.profile is None:
            ops = self.full
        times = ops.times
        if event not in times:
            if self.profile is not None:
                times[event] = self.timing.sync_event_time(
                    event, self.proto.alpha, participants=ops.pace,
                    clusters=self.proto.clusters, t=ops.t,
                )
            elif self.latency is None:
                times[event] = 0.0
            else:
                lat = self.latency
                t = lat.t_comp()
                if event in ("intra", "inter"):
                    t += lat.t_comm_client_server()
                if event == "inter":
                    t += self.proto.alpha * lat.t_comm_server_server()
                times[event] = t
        return times[event]

    def round_time(self, ops: Optional[RoundOperands] = None) -> float:
        """One round's wall-clock: the protocol's event schedule over
        ``tau1 * tau2`` iterations, without the uplink penalty."""
        ops = self.full if ops is None else ops
        if "round" not in ops.times:
            proto = self.proto
            ops.times["round"] = sum(
                self.event_time(proto.event_at(i), ops)
                for i in range(1, proto.tau1 * proto.tau2 + 1)
            )
        return ops.times["round"]

    def superstep_time(self, records) -> float:
        """Wall-clock of consecutive rounds, uplink penalties included."""
        if self._sum_rounds:
            dt = sum(self.round_time(ops) for ops in records)
        else:
            dt = len(records) * self.round_time(records[0])
        return dt + sum(ops.penalty for ops in records)
