"""Fault-injection subsystem: schedule compilation, scheduler integration.

Three layers of coverage:

* ``FaultSchedule`` in isolation — event validation, windowed heal / rejoin
  replay, per-component mixing matrices, ring-stencil gating, spec
  resolution;
* the schedulers — faulted round == faulted sync, all three aggregation
  backends agree under a fault trace, the whole ring -> line -> ring churn
  reuses ONE compiled superstep, an empty schedule is bitwise the
  fault-free path, async outages skip the dead cluster, and a mid-outage
  checkpoint resume replays to identical fp32 parameters;
* the degradation surfaces — uplink retry pricing and the serving layer's
  last-good weight retention.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClusterSpec, make_run, ring
from repro.core.topology import from_edges
from repro.data import FederatedDataset, iid_partition, mnist_like
from repro.faults import (
    FaultSchedule, resolve_faults, validate_fault_events,
)
from repro.models import MnistCNN

D, C = 4, 8

# ring -> line (link cut) -> one server dark (staleness rejoin) -> a crash
# and an uplink drop: every registered kind inside 8 rounds
TRACE = [
    {"kind": "link-down", "round": 1, "link": [0, 3], "until": 4},
    {"kind": "server-down", "round": 2, "server": 2, "until": 5},
    {"kind": "client-crash", "round": 2, "client": 5, "until": 6},
    {"kind": "uplink-drop", "round": 3, "client": 1},
]


@pytest.fixture(scope="module")
def fed_data():
    data = mnist_like(400, seed=0)
    train, _ = data.split(0.9)
    parts = iid_partition(train.y, C)
    return FederatedDataset(train, parts)


def _batches(ds, seed=700):
    """Deterministic per-iteration stream: every arm sees identical data."""
    return lambda i: ds.stacked_batch(4, np.random.default_rng(seed + i))


def _spec():
    return ClusterSpec.uniform(C, D)


def _round_cfg(**kw):
    cfg = {"scheduler": "round", "model": MnistCNN(), "num_clients": C,
           "num_clusters": D, "tau1": 2, "tau2": 1, "alpha": 1,
           "topology": "ring", "learning_rate": 0.05, "seed": 0,
           "rounds_per_step": 2, "faults": TRACE}
    cfg.update(kw)
    return cfg


# ---------------------------------------------------------------------------
# Event validation + spec resolution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad, match", [
    ([{"kind": "power-surge", "round": 0, "server": 1}], "unknown kind"),
    ([{"kind": "link-down", "round": 0}], "missing 'link'"),
    ([{"kind": "server-down", "round": 0, "server": 1, "client": 2}],
     "unexpected operand"),
    ([{"kind": "link-down", "round": -1, "link": [0, 1]}], "round must be"),
    ([{"kind": "link-down", "round": 0, "link": [1, 1]}], "distinct servers"),
    ([{"kind": "server-down", "round": 3, "server": 0, "until": 2}],
     "until must be"),
    ([{"kind": "uplink-drop", "round": 0, "client": 1, "until": 4}],
     "'until' not supported"),
    ([{"kind": "link-down", "round": 0, "link": [0, 1], "frequency": 2}],
     "unknown fields"),
    ("not-a-list", "must be a list"),
])
def test_validate_fault_events_rejects(bad, match):
    with pytest.raises(ValueError, match=match):
        validate_fault_events(bad)


def test_schedule_range_checks():
    topo, spec = ring(D), _spec()
    for ev, match in [
        ({"kind": "link-down", "round": 0, "link": [0, 9]}, "out of range"),
        ({"kind": "server-down", "round": 0, "server": D}, "out of range"),
        ({"kind": "client-crash", "round": 0, "client": C}, "out of range"),
    ]:
        with pytest.raises(ValueError, match=match):
            FaultSchedule(topo, spec, [ev])
    with pytest.raises(ValueError, match="unknown psi"):
        FaultSchedule(topo, spec, [], psi="optimism")


def test_resolve_faults_forms():
    topo, spec = ring(D), _spec()
    # empty schedules resolve to None: the fault-free code path, literally
    for empty in (None, [], "[]", {"events": []}):
        assert resolve_faults(empty, topo, spec) is None
    sched = resolve_faults(TRACE, topo, spec)
    assert isinstance(sched, FaultSchedule)
    # JSON string and {"events": ...} dict resolve to the same trace
    import json
    for form in (json.dumps(TRACE), {"events": TRACE}):
        assert resolve_faults(form, topo, spec).describe() == sched.describe()
    with pytest.raises(ValueError, match="not valid JSON"):
        resolve_faults("{broken", topo, spec)
    with pytest.raises(ValueError, match="unknown keys"):
        resolve_faults({"events": [], "jitter": 1}, topo, spec)
    # a prebuilt schedule is size-checked against the scenario
    with pytest.raises(ValueError, match="built for"):
        resolve_faults(sched, ring(6), ClusterSpec.uniform(12, 6))


def test_make_run_rejects_malformed_faults():
    with pytest.raises(ValueError, match="unknown kind"):
        make_run(_round_cfg(faults=[{"kind": "gremlin", "round": 0,
                                     "server": 1}]))


# ---------------------------------------------------------------------------
# Per-round state replay
# ---------------------------------------------------------------------------

def test_adjacency_window_and_heal():
    sched = FaultSchedule(ring(D), _spec(), TRACE)
    a0 = sched.adjacency_at(0)
    np.testing.assert_array_equal(a0, ring(D).adjacency)
    # rounds 1-3: link (0, 3) gone; round 4: healed (but server 2 still dark)
    assert sched.adjacency_at(1)[0, 3] == 0 and sched.adjacency_at(1)[3, 0] == 0
    assert sched.adjacency_at(4)[0, 3] == 1
    # rounds 2-4: server 2 takes all its links down with it
    for r in (2, 3, 4):
        assert not sched.server_alive(r)[2]
        assert sched.adjacency_at(r)[2].sum() == 0
        assert sched.adjacency_at(r)[:, 2].sum() == 0
    assert sched.server_alive(5)[2]
    assert sched.horizon() == 6
    # client masks: crash spans rounds 2-5, the uplink drop only round 3
    assert sched.client_mask(1)[5] and not sched.client_mask(2)[5]
    assert not sched.client_mask(3)[1] and sched.client_mask(4)[1]
    np.testing.assert_array_equal(
        sched.uplink_failed(3), np.arange(C) == 1)
    assert not sched.uplink_failed(4).any()


def test_mixing_per_component():
    # cut the 4-ring 0-1-2-3-0 into islands {1, 2} and {3, 0}
    events = [{"kind": "link-down", "round": 0, "link": [0, 1]},
              {"kind": "link-down", "round": 0, "link": [2, 3]}]
    spec = ClusterSpec(C, (0, 0, 1, 1, 2, 2, 3, 3),
                       (1.0, 1.0, 2.0, 2.0, 3.0, 3.0, 4.0, 4.0))
    sched = FaultSchedule(ring(D), spec, events)
    p = sched.mixing_at(0)
    # cross-component entries are exactly zero; columns sum to 1
    for i, j in [(0, 1), (1, 0), (2, 3), (3, 2)]:
        assert p[i, j] == 0.0 and p[j, i] == 0.0
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-12)
    # each island's renormalized weighted mean is its fixed point
    ratios = np.asarray(spec.m_tilde())
    for comp in ([1, 2], [3, 0]):
        r = ratios[comp] / ratios[comp].sum()
        np.testing.assert_allclose(p[np.ix_(comp, comp)] @ r, r, atol=1e-12)


def test_rejoin_round_uses_staleness_blend():
    sched = FaultSchedule(ring(D), _spec(),
                          [{"kind": "server-down", "round": 1, "server": 2,
                            "until": 4}])
    assert sched.rejoined_at(4) == {2: 3}
    assert sched.rejoined_at(5) == {}
    p4, p5 = sched.mixing_at(4), sched.mixing_at(5)
    # both are valid mixers, but the rejoin round blends by staleness (the
    # 3-round-stale model is NOT reabsorbed at full eq-5 weight)
    for p in (p4, p5):
        np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-12)
    assert not np.allclose(p4, p5)
    # the stale model is blended back at *reduced* weight: the rejoiner
    # takes in the fresh consensus instead of keeping (or broadcasting)
    # its 3-round-old model at full eq-5 weight
    assert p4[2, 2] < p5[2, 2]
    # explicit server-up replays the same gap bookkeeping
    sched2 = FaultSchedule(ring(D), _spec(),
                           [{"kind": "server-down", "round": 1, "server": 2},
                            {"kind": "server-up", "round": 4, "server": 2}])
    assert sched2.rejoined_at(4) == {2: 3}
    np.testing.assert_allclose(sched2.mixing_at(4), p4, atol=0)


def test_mixing_stack_ring_stencil_gate():
    line = FaultSchedule(ring(D), _spec(), TRACE)
    # link cuts / outages only *remove* ring edges: stencil-safe
    stack = line.mixing_stack(0, 8, require_ring_stencil=True)
    assert stack.shape == (8, D, D) and stack.dtype == np.float32
    # a rewired chord leaves the stencil -> the collective backend must
    # refuse at bind time, naming the offending round
    chord = FaultSchedule(ring(6), ClusterSpec.uniform(12, 6),
                          [{"kind": "link-up", "round": 2, "link": [0, 3]}])
    with pytest.raises(ValueError, match="round 2"):
        chord.mixing_stack(0, 4, require_ring_stencil=True)


def test_faults_with_nonring_topology():
    topo = from_edges(4, [(0, 1), (1, 2), (2, 3), (3, 0), (0, 2)])
    sched = FaultSchedule(topo, _spec(),
                          [{"kind": "link-down", "round": 0, "link": [0, 2]}])
    np.testing.assert_array_equal(sched.adjacency_at(0), ring(4).adjacency)


# ---------------------------------------------------------------------------
# Scheduler integration (round / sync / async)
# ---------------------------------------------------------------------------

def _run_round(ds, steps=4, **kw):
    rt = make_run(_round_cfg(**kw))
    bs = _batches(ds)
    for k in range(1, steps + 1):
        ev = rt.scheduler.step(k, bs)
        assert np.isfinite(np.asarray(ev.losses)).all()
    return rt


def test_empty_schedule_is_bitwise_fault_free(fed_data):
    rt_none = _run_round(fed_data, faults=None)
    rt_empty = _run_round(fed_data, faults=[])
    for a, b in zip(jax.tree.leaves(rt_none.global_params()),
                    jax.tree.leaves(rt_empty.global_params())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fault_trace_reuses_one_compiled_superstep(fed_data):
    rt = _run_round(fed_data, steps=4)  # 8 rounds: covers the whole trace
    assert rt.scheduler._round_step._cache_size() == 1
    # and the faults genuinely changed the trajectory
    clean = _run_round(fed_data, steps=4, faults=None)
    diff = max(
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(jax.tree.leaves(rt.global_params()),
                        jax.tree.leaves(clean.global_params()))
    )
    assert diff > 0.0


@pytest.mark.parametrize("backend", ["pallas", "collective"])
def test_backends_agree_under_faults(fed_data, backend):
    ref = _run_round(fed_data, backend="dense")
    got = _run_round(fed_data, backend=backend)
    for a, b in zip(jax.tree.leaves(ref.global_params()),
                    jax.tree.leaves(got.global_params())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_sync_matches_round_under_faults(fed_data):
    rt_round = _run_round(fed_data, rounds_per_step=1, steps=8)
    rt_sync = make_run({
        "scheduler": "sync", "model": MnistCNN(),
        "clusters": _spec(), "topology": "ring",
        "tau1": 2, "tau2": 1, "alpha": 1, "learning_rate": 0.05,
        "seed": 0, "faults": TRACE,
    })
    bs = _batches(fed_data)
    for k in range(1, 17):  # 16 iterations == 8 tau1*tau2 rounds
        rt_sync.scheduler.step(k, bs)
    for a, b in zip(jax.tree.leaves(rt_round.global_params()),
                    jax.tree.leaves(rt_sync.global_params())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_async_outage_skips_dead_cluster(fed_data):
    from repro.data import ClientBatcher

    rt = make_run({
        "scheduler": "async", "model": MnistCNN(),
        "clusters": ClusterSpec(C, (0, 0, 1, 1, 2, 2, 3, 3),
                                fed_data.data_sizes()),
        "topology": "ring", "learning_rate": 0.05, "min_batches": 1,
        "heterogeneity": 2.0, "seed": 0,
        "faults": [{"kind": "server-down", "round": 2, "server": 1,
                    "until": 6}],
    })
    bs = ClientBatcher(fed_data, 4, seed=0)
    kinds = [rt.scheduler.step(k, bs).kind for k in range(1, 11)]
    assert "outage" in kinds          # the dead server's events are skipped
    assert "cluster" in kinds         # everyone else keeps training
    # outage events do not advance the protocol iteration count
    assert rt.scheduler.t == sum(k == "cluster" for k in kinds)
    g = rt.global_params()
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))


def test_faults_require_resident_store(fed_data):
    with pytest.raises(ValueError, match="resident"):
        make_run(_round_cfg(store={"kind": "host-offload", "k_max": 4}))


# ---------------------------------------------------------------------------
# Checkpoint resume mid-outage
# ---------------------------------------------------------------------------

def test_mid_outage_resume_is_bitwise(fed_data, tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    steps, mid = 4, 2  # superstep 2 ends at round 4: server 2 still dark
    ref = _run_round(fed_data, steps=steps)

    rt_a = _run_round(fed_data, steps=mid)
    sched_a = rt_a.scheduler
    save_checkpoint(str(tmp_path), {"params": sched_a.params,
                                    "opt_state": sched_a.opt_state},
                    step=mid, metadata={"faults": sched_a.faults.describe()})

    rt_b = make_run(_round_cfg())
    sched_b = rt_b.scheduler
    state, manifest = restore_checkpoint(
        str(tmp_path), {"params": sched_b.params,
                        "opt_state": sched_b.opt_state})
    # the metadata copy pins the fault sequence across the restart
    assert manifest["metadata"]["faults"] == sched_b.faults.describe()
    sched_b.params, sched_b.opt_state = state["params"], state["opt_state"]
    bs = _batches(fed_data)
    for k in range(mid + 1, steps + 1):
        sched_b.step(k, bs)
    for a, b in zip(jax.tree.leaves(ref.scheduler.params),
                    jax.tree.leaves(sched_b.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# Degradation pricing + serving retention
# ---------------------------------------------------------------------------

def test_uplink_retry_penalty():
    from repro.core import MNIST_LATENCY
    from repro.hetero import sample_profile
    from repro.hetero.timing import MAX_ATTEMPTS, FleetTiming

    profile = sample_profile("bimodal-straggler", C, seed=0)
    timing = FleetTiming(profile, MNIST_LATENCY)
    none = np.zeros(C, dtype=bool)
    assert timing.uplink_retry_penalty(none) == 0.0
    failed = none.copy()
    failed[2] = failed[6] = True
    want = (MAX_ATTEMPTS - 1) * MNIST_LATENCY.t_comm_client_server(
        float(profile.bandwidths[[2, 6]].min()))
    assert timing.uplink_retry_penalty(failed) == pytest.approx(want)
    assert want > 0
    # no latency model -> pricing is off, faults cost nothing
    assert FleetTiming(profile).uplink_retry_penalty(failed) == 0.0


def test_serving_keeps_last_good_on_faulty_publish():
    from repro.configs import get_config
    from repro.models import CausalLM
    from repro.serving import FederatedServer

    model = CausalLM(get_config("qwen2.5-3b").reduced())
    p = model.init(jax.random.PRNGKey(0))
    stack = jax.tree.map(lambda x: jnp.stack([x, x + 0.01]), p)
    srv = FederatedServer(model, stack)
    before = jax.tree.leaves(srv.active_params)[0]

    poisoned = jax.tree.map(lambda x: x.at[0].set(jnp.nan), stack)
    with pytest.raises(ValueError, match="non-finite"):
        srv.publish(poisoned)

    class DyingRuntime:
        def cluster_params(self):
            raise RuntimeError("training source died mid-round")

    class PoisonedRuntime:
        def cluster_params(self):
            return poisoned

    for rt in (DyingRuntime(), PoisonedRuntime()):
        assert srv.sync_from(rt) is False
    assert srv.rejected == 2

    class DeviceOutOfMemory:
        def cluster_params(self):
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    # a failing device is not a dying trainer: it must not pass as a reject
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        srv.sync_from(DeviceOutOfMemory())
    assert srv.rejected == 2
    # the active slot never saw the bad stacks
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(srv.active_params)[0]),
        np.asarray(before))
    with pytest.raises(ValueError, match="no runtime attached"):
        srv.sync_from()


def test_chaos_ring_scenario_registered():
    from repro.scenarios import get_scenario

    sc = get_scenario("chaos-ring")
    kinds = {e["kind"] for e in sc.faults["events"]}
    assert {"link-down", "server-down", "client-crash", "uplink-drop"} <= kinds
    cfg = sc.config(num_clients=8, num_clusters=4)
    assert cfg["faults"] is sc.faults
