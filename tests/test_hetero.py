"""Device-heterogeneity subsystem tests: profiles, timing, async threading."""
import numpy as np
import pytest

from repro.core import AsyncConfig, ClusterSpec, MNIST_LATENCY, make_run, ring
from repro.hetero import (
    ClusterDropout, DeviceProfile, FleetTiming, PROFILE_REGISTRY, sample_profile,
)


# ---------------------------------------------------------------------------
# Profiles + samplers
# ---------------------------------------------------------------------------

def test_registry_has_all_paper_samplers():
    assert {"uniform", "bimodal-straggler", "exponential", "trace"} <= set(
        PROFILE_REGISTRY
    )


@pytest.mark.parametrize("kind", ["uniform", "bimodal-straggler", "exponential"])
def test_samplers_normalized_and_deterministic(kind):
    a = sample_profile(kind, 24, seed=3)
    b = sample_profile(kind, 24, seed=3)
    c = sample_profile(kind, 24, seed=4)
    np.testing.assert_array_equal(a.speeds, b.speeds)
    np.testing.assert_array_equal(a.bandwidths, b.bandwidths)
    assert not np.array_equal(a.speeds, c.speeds)  # seed actually matters
    # paper normalization: slowest device is the reference CPU
    assert a.speeds.min() == pytest.approx(1.0)
    assert a.num_clients == 24
    assert np.all(a.availability > 0) and np.all(a.availability <= 1)


def test_uniform_profile_heterogeneity_gap():
    # the requested gap must be realized exactly for every seed (the
    # extreme pins use distinct indices)
    for seed in range(20):
        p = sample_profile({"kind": "uniform", "heterogeneity": 7.0}, 8, seed=seed)
        assert p.heterogeneity() == pytest.approx(7.0)
    flat = sample_profile({"kind": "uniform", "heterogeneity": 1.0}, 10)
    assert np.all(flat.speeds == 1.0)


def test_bimodal_straggler_structure():
    p = sample_profile(
        {"kind": "bimodal-straggler", "straggler_frac": 0.25, "speedup": 8.0,
         "straggler_bandwidth": 0.5},
        16, seed=1,
    )
    slow = p.speeds == 1.0
    assert slow.sum() == 4                       # 25% of 16
    assert np.all(p.speeds[~slow] == 8.0)
    assert np.all(p.bandwidths[slow] == 0.5)     # stragglers on degraded links
    assert np.all(p.bandwidths[~slow] == 1.0)
    assert p.heterogeneity() == pytest.approx(8.0)


def test_trace_profile_cycles_and_requires_speeds():
    p = sample_profile({"kind": "trace", "speeds": [1.0, 2.0, 4.0]}, 7)
    assert p.num_clients == 7
    np.testing.assert_allclose(p.speeds, [1, 2, 4, 1, 2, 4, 1])
    assert p.schedule is None                 # static traces stay static
    with pytest.raises(ValueError, match="speeds"):
        sample_profile("trace", 4)


def test_trace_profile_time_varying_schedule():
    """2-D trace arrays attach a TraceSchedule (ROADMAP open item)."""
    from repro.hetero import TraceSchedule

    speeds = np.array([[1.0, 2.0], [2.0, 8.0], [1.0, 2.0]])
    avail = np.array([[1.0, 1.0], [1.0, 0.0], [0.5, 1.0]])
    p = sample_profile(
        {"kind": "trace", "speeds": speeds, "availability": avail}, 4
    )
    sched = p.schedule
    assert isinstance(sched, TraceSchedule)
    assert sched.num_steps == 3 and sched.num_clients == 4
    # columns cycle over the fleet; global min pins the reference device
    np.testing.assert_allclose(sched.speeds_at(0), [1, 2, 1, 2])
    np.testing.assert_allclose(sched.speeds_at(1), [2, 8, 2, 8])
    np.testing.assert_allclose(sched.speeds_at(3), sched.speeds_at(0))  # cycles
    np.testing.assert_allclose(sched.availability_at(1), [1, 0, 1, 0])
    # static columns are the schedule's per-client time averages
    np.testing.assert_allclose(p.speeds, sched.speeds.mean(axis=0))
    np.testing.assert_allclose(p.availability, sched.availability.mean(axis=0))
    # a 1-D availability broadcasts across the schedule rows
    p2 = sample_profile(
        {"kind": "trace", "speeds": speeds, "availability": [0.5, 1.0]}, 2
    )
    np.testing.assert_allclose(p2.schedule.availability_at(1), [0.5, 1.0])
    # mismatched row counts align on their least common multiple
    p3 = sample_profile(
        {"kind": "trace",
         "speeds": np.ones((2, 2)),
         "availability": np.tile([[1.0, 1.0], [0.0, 0.0], [1.0, 0.0]], (1, 1))},
        2,
    )
    assert p3.schedule.num_steps == 6
    np.testing.assert_allclose(p3.schedule.availability_at(5), [1.0, 0.0])


def test_trace_schedule_validation():
    from repro.hetero import TraceSchedule

    with pytest.raises(ValueError, match="2-D"):
        TraceSchedule(np.ones(3), np.ones(3))
    with pytest.raises(ValueError, match="positive"):
        TraceSchedule(np.zeros((2, 2)), np.ones((2, 2)))
    with pytest.raises(ValueError, match="0, 1"):
        TraceSchedule(np.ones((2, 2)), 2 * np.ones((2, 2)))


def test_sample_profile_validation():
    with pytest.raises(KeyError, match="unknown device profile"):
        sample_profile("warp-speed", 8)
    ready = DeviceProfile.homogeneous(8)
    assert sample_profile(ready, 8) is ready
    with pytest.raises(ValueError, match="clients"):
        sample_profile(ready, 9)
    assert sample_profile(None, 5).heterogeneity() == 1.0


def test_profile_field_validation():
    ones = np.ones(4)
    with pytest.raises(ValueError, match="positive"):
        DeviceProfile(np.array([1.0, -1.0, 1.0, 1.0]), ones, ones)
    with pytest.raises(ValueError, match="availability"):
        DeviceProfile(ones, ones, np.array([0.5, -0.1, 1.0, 1.0]))
    with pytest.raises(ValueError, match="length"):
        DeviceProfile(ones, np.ones(3), ones)
    # 0 is legal: a permanently-dead client is meaningful under sampling
    dead = DeviceProfile(ones, ones, np.array([0.5, 0.0, 1.0, 1.0]))
    assert dead.availability[1] == 0.0


def test_effective_speeds_discount_availability():
    p = DeviceProfile(np.array([1.0, 4.0]), np.ones(2), np.array([1.0, 0.5]))
    np.testing.assert_allclose(p.effective_speeds(), [1.0, 2.0])


# ---------------------------------------------------------------------------
# FleetTiming
# ---------------------------------------------------------------------------

def test_sync_pacing_follows_slowest_effective_client():
    fast = DeviceProfile(np.full(4, 10.0), np.ones(4), np.ones(4))
    mixed = DeviceProfile(np.array([1.0, 10.0, 10.0, 10.0]), np.ones(4), np.ones(4))
    t_fast = FleetTiming(fast, MNIST_LATENCY).sync_event_time("local")
    t_mixed = FleetTiming(mixed, MNIST_LATENCY).sync_event_time("local")
    assert t_mixed == pytest.approx(10 * t_fast)  # one straggler paces everyone
    # narrow uplink stretches aggregation events only
    narrow = DeviceProfile(np.ones(4), np.array([0.25, 1, 1, 1]), np.ones(4))
    ft = FleetTiming(narrow, MNIST_LATENCY)
    assert ft.sync_event_time("intra") == pytest.approx(
        MNIST_LATENCY.t_comp() + 4 * 6.4
    )
    assert ft.sync_event_time("local") == pytest.approx(MNIST_LATENCY.t_comp())


def test_cluster_service_times_per_cluster_pacing():
    # cluster 0: clients 0-1 (slow, narrow); cluster 1: clients 2-3 (fast)
    spec = ClusterSpec.uniform(4, 2)
    prof = DeviceProfile(
        np.array([1.0, 2.0, 8.0, 8.0]),
        np.array([0.5, 1.0, 1.0, 1.0]),
        np.ones(4),
    )
    times = FleetTiming(prof, MNIST_LATENCY).cluster_service_times(spec, 2)
    expected0 = 2 * MNIST_LATENCY.t_comp(1.0) + 6.4 / 0.5 + 0.64
    expected1 = 2 * MNIST_LATENCY.t_comp(8.0) + 6.4 + 0.64
    np.testing.assert_allclose(times, [expected0, expected1])
    assert times[0] > times[1]


def test_dropout_process_geometric_and_deterministic():
    avail = np.array([1.0, 0.3])
    a = ClusterDropout(avail, seed=7)
    b = ClusterDropout(avail, seed=7)
    draws_a = [a.attempts(1) for _ in range(50)]
    draws_b = [b.attempts(1) for _ in range(50)]
    assert draws_a == draws_b                       # deterministic per seed
    assert all(d >= 1 for d in draws_a)
    assert max(draws_a) > 1                         # flaky device does retry
    assert all(a.attempts(0) == 1 for _ in range(10))  # available: no retries
    from repro.hetero.timing import MAX_ATTEMPTS
    assert max(draws_a) <= MAX_ATTEMPTS


def test_zero_availability_guarded_not_divided():
    """availability == 0 prices at the retry cap (no division, no infinity)."""
    from repro.hetero.timing import MAX_ATTEMPTS

    dead = ClusterDropout(np.array([0.0, 1.0]), seed=0)
    assert all(dead.attempts(0) == MAX_ATTEMPTS for _ in range(5))
    prof = DeviceProfile(np.ones(4), np.ones(4),
                         np.array([0.0, 1.0, 1.0, 1.0]))
    t = FleetTiming(prof, MNIST_LATENCY).sync_event_time("inter", alpha=2)
    assert np.isfinite(t)
    # the dead device paces at speed 1/MAX_ATTEMPTS, not infinitely slowly
    assert t == pytest.approx(
        MNIST_LATENCY.t_comp(1.0 / MAX_ATTEMPTS)
        + MNIST_LATENCY.t_comm_client_server()
        + 2 * MNIST_LATENCY.t_comm_server_server()
    )


# ---------------------------------------------------------------------------
# Threading into the engines
# ---------------------------------------------------------------------------

def test_async_config_iter_times_use_profile_bandwidths():
    spec = ClusterSpec.uniform(4, 2)
    prof = DeviceProfile(
        np.ones(4), np.array([0.5, 1.0, 1.0, 1.0]), np.ones(4)
    )
    base = AsyncConfig(clusters=spec, topology=ring(2), speeds=np.ones(4),
                       min_batches=2, alpha_latency=MNIST_LATENCY)
    with_prof = AsyncConfig(clusters=spec, topology=ring(2), min_batches=2,
                            alpha_latency=MNIST_LATENCY, profile=prof)
    np.testing.assert_array_equal(with_prof.speeds, prof.speeds)
    t_base, t_prof = base.iter_times(), with_prof.iter_times()
    assert t_prof[0] > t_base[0]                 # narrow uplink slows cluster 0
    assert t_prof[1] == pytest.approx(t_base[1])
    # theta derives from profile speeds
    assert np.all(with_prof.theta() >= 1)


def test_async_config_size_mismatch_raises():
    spec = ClusterSpec.uniform(4, 2)
    with pytest.raises(ValueError, match="profile size"):
        AsyncConfig(clusters=spec, topology=ring(2),
                    profile=DeviceProfile.homogeneous(5))
    with pytest.raises(ValueError, match="one speed per client"):
        AsyncConfig(clusters=spec, topology=ring(2), speeds=np.ones(3))
    # theta() reads speeds while iter_times() prices from the profile, so an
    # ambiguous double source is rejected outright
    with pytest.raises(ValueError, match="not both"):
        AsyncConfig(clusters=spec, topology=ring(2), speeds=np.ones(4),
                    profile=DeviceProfile.homogeneous(4))


def _tiny_async_run(profile_spec, events=8, seed=0):
    from repro.data import ClientBatcher, FederatedDataset, iid_partition, mnist_like
    from repro.models import MnistCNN

    data = mnist_like(300, seed=0)
    parts = iid_partition(data.y, 8, seed=0)
    ds = FederatedDataset(data, parts)
    spec = ClusterSpec(8, (0, 0, 1, 1, 2, 2, 3, 3), ds.data_sizes())
    rt = make_run({
        "scheduler": "async", "model": MnistCNN(), "clusters": spec,
        "topology": "ring", "profile": profile_spec, "profile_seed": seed,
        "min_batches": 2, "theta_max": 4, "latency": MNIST_LATENCY,
        "seed": seed,
    })
    batcher = ClientBatcher(ds, 4, seed=seed)
    events_seen = [rt.step(batcher) for _ in range(events)]
    return rt, events_seen


def test_async_scheduler_straggler_fleet_orders_events():
    """Fast clusters fire more often than the straggler cluster."""
    rt, events = _tiny_async_run(
        {"kind": "bimodal-straggler", "straggler_frac": 0.25, "speedup": 6.0},
        events=16,
    )
    sched = rt.scheduler
    # per-cluster service times differ (profile threaded into the queue)
    assert sched.iter_times.max() > sched.iter_times.min()
    counts = np.bincount([e.cluster for e in events], minlength=4)
    assert counts[np.argmin(sched.iter_times)] >= counts[np.argmax(sched.iter_times)]
    # iteration gaps consumed by the staleness mixing are non-degenerate
    assert sched.t == 16
    assert (sched.t - sched.last_update).max() >= 1


def test_async_scheduler_dropout_stretches_gaps():
    """Low availability inflates the simulated clock vs. the same fleet up."""
    rt_up, _ = _tiny_async_run({"kind": "uniform", "heterogeneity": 3.0}, events=12)
    rt_flaky, _ = _tiny_async_run(
        {"kind": "uniform", "heterogeneity": 3.0, "availability": 0.4}, events=12
    )
    assert rt_flaky.scheduler._dropout is not None
    assert rt_up.scheduler._dropout is None
    assert rt_flaky.scheduler.clock > rt_up.scheduler.clock


def test_sync_scheduler_profile_pacing_via_make_run():
    from repro.models import MnistCNN

    base = {
        "scheduler": "sync", "model": MnistCNN(),
        "num_clients": 8, "num_clusters": 4, "topology": "ring",
        "tau1": 2, "latency": MNIST_LATENCY, "seed": 0,
    }
    rt_plain = make_run(dict(base))
    rt_prof = make_run(dict(
        base, profile={"kind": "bimodal-straggler", "speedup": 10.0,
                       "straggler_bandwidth": 0.5},
    ))
    t_plain = rt_plain.scheduler.iteration_time("intra")
    t_prof = rt_prof.scheduler.iteration_time("intra")
    assert t_prof > t_plain                      # straggler + narrow link pace
    assert t_prof == pytest.approx(MNIST_LATENCY.t_comp() + 2 * 6.4)


def test_round_scheduler_profile_round_time():
    from repro.core import RoundScheduler
    from repro.core.sdfeel import FLSpec
    from repro.models import MnistCNN

    fl = FLSpec(num_clients=4, num_clusters=2, tau1=2, tau2=1, alpha=1)
    plain = RoundScheduler(fl, latency=MNIST_LATENCY)
    plain.bind(MnistCNN(), seed=0)
    prof = RoundScheduler(
        fl, latency=MNIST_LATENCY,
        profile=sample_profile({"kind": "bimodal-straggler", "speedup": 4.0}, 4),
    )
    prof.bind(MnistCNN(), seed=0)
    assert prof.round_time() > 0
    assert prof.round_time() >= plain.round_time()


# ---------------------------------------------------------------------------
# Time-varying trace pricing: rounds pay their own row, not the average
# ---------------------------------------------------------------------------

def test_fleet_timing_prices_trace_rounds_individually():
    from repro.hetero import TraceSchedule

    n = 4
    speeds = np.vstack([np.full(n, 4.0), np.full(n, 1.0)])
    ones = np.ones((2, n))
    prof = DeviceProfile(
        speeds=speeds.mean(axis=0), availability=ones.mean(axis=0),
        bandwidths=np.ones(n), schedule=TraceSchedule(speeds, ones),
    )
    ft = FleetTiming(prof, MNIST_LATENCY)
    t0 = ft.sync_event_time("local", t=0)
    t1 = ft.sync_event_time("local", t=1)
    assert t1 == pytest.approx(4 * t0)             # the slow row costs 4x
    assert ft.sync_event_time("local", t=2) == pytest.approx(t0)  # cycles
    # t=None keeps the static time-average pricing bit-identical
    static = DeviceProfile(speeds=speeds.mean(axis=0),
                           availability=ones.mean(axis=0),
                           bandwidths=np.ones(n))
    assert ft.sync_event_time("local") == FleetTiming(
        static, MNIST_LATENCY).sync_event_time("local")
    # the round's availability row discounts that round's speeds
    avail = np.vstack([np.full(n, 0.5), np.ones(n)])
    flaky = DeviceProfile(
        speeds=speeds.mean(axis=0), availability=avail.mean(axis=0),
        bandwidths=np.ones(n), schedule=TraceSchedule(speeds, avail),
    )
    assert FleetTiming(flaky, MNIST_LATENCY).sync_event_time(
        "local", t=0) == pytest.approx(2 * t0)


def test_sync_scheduler_prices_trace_per_round():
    """StepEvent.dt follows the trace row of the step's round."""
    from repro.models import MnistCNN

    rt = make_run({
        "scheduler": "sync", "model": MnistCNN(),
        "num_clients": 4, "num_clusters": 2, "topology": "ring",
        "tau1": 1, "tau2": 1, "latency": MNIST_LATENCY,
        "profile": {"kind": "trace",
                    "speeds": [[4.0] * 4, [1.0] * 4],
                    "availability": [[1.0] * 4, [1.0] * 4]},
        "seed": 0,
    })
    rng = np.random.default_rng(0)

    def batch(k):
        return {"x": rng.normal(size=(4, 2, 28, 28, 1)).astype(np.float32),
                "y": rng.integers(0, 10, size=(4, 2)).astype(np.int32)}

    e1 = rt.scheduler.step(1, batch)    # round 0: fast row
    e2 = rt.scheduler.step(2, batch)    # round 1: slow row
    e3 = rt.scheduler.step(3, batch)    # round 2: trace cycles back
    assert e2.dt > e1.dt
    assert e3.dt == pytest.approx(e1.dt)
    # compute term scales with the row's speed; comm terms are unchanged
    assert e2.dt - e1.dt == pytest.approx(
        MNIST_LATENCY.t_comp(1.0) - MNIST_LATENCY.t_comp(4.0))


def test_round_scheduler_prices_trace_per_round():
    from repro.core import RoundScheduler
    from repro.core.sdfeel import FLSpec
    from repro.models import MnistCNN

    fl = FLSpec(num_clients=4, num_clusters=2, tau1=2, tau2=1, alpha=1)
    prof = sample_profile(
        {"kind": "trace", "speeds": [[4.0] * 4, [1.0] * 4],
         "availability": [[1.0] * 4, [1.0] * 4]}, 4)
    sched = RoundScheduler(fl, latency=MNIST_LATENCY, profile=prof,
                           rounds_per_step=2)
    sched.bind(MnistCNN(), seed=0)
    rounds = sched.rounds

    def round_time_at(r):
        return rounds.round_time(rounds.operands(r))

    r0, r1 = round_time_at(0), round_time_at(1)
    assert r1 > r0
    assert round_time_at(2) == pytest.approx(r0)   # cycles
    # the static average lies strictly between the two rows
    assert r0 < sched.round_time() < r1
    # a 2-round superstep is billed row by row, not 2x either row
    rng = np.random.default_rng(0)

    def batch(k):
        return {"x": rng.normal(size=(4, 2, 28, 28, 1)).astype(np.float32),
                "y": rng.integers(0, 10, size=(4, 2)).astype(np.int32)}

    ev = sched.step(1, batch)
    assert ev.dt == pytest.approx(r0 + r1)
