"""The bound fleet (``repro.core.rounds.FleetRounds``): one rule for who is
in round ``r``, with what weights, over what mixing and at what price,
shared by the sync, round and async schedulers.

* the sync scheduler's per-iteration clock and the round scheduler's
  per-round clock agree over every fleet axis, and a superstep is the sum of
  its rounds;
* ``client_mask`` composes the plan with the fault schedule, and a fully
  crashed cluster falls back to its ``m^`` column;
* each event kind is priced once per round;
* the static round step is called with exactly ``(params, opt_state,
  batches)``, which the benchmark harness wraps.
"""
import numpy as np
import pytest
from test_faults import TRACE

from repro.core import MNIST_LATENCY, make_run
from repro.core.config import ExecSpec, FleetSpec, ModelSpec, RunConfig
from repro.data import FederatedDataset, iid_partition, mnist_like
from repro.hetero import FleetTiming
from repro.models import MnistCNN

C, D = 8, 4
BIMODAL = {"kind": "bimodal-straggler", "speedup": 10.0,
           "straggler_bandwidth": 0.5}
TRACE_PROFILE = {"kind": "trace",
                 "speeds": [[4.0] * C, [1.0] * C, [2, 1, 4, 3, 1, 2, 8, 1]],
                 "availability": [[1.0] * C, [0.5] * C, [1.0] * C]}
UNIFORM_K = {"strategy": "uniform-k", "k": 1, "seed": 3}

FLEETS = {
    "no-profile": {},
    "bimodal": {"profile": BIMODAL},
    "trace": {"profile": TRACE_PROFILE},
    "uniform-k": {"profile": BIMODAL, "participation": UNIFORM_K},
    "faults": {"profile": BIMODAL, "faults": TRACE},
    "offload": {"profile": BIMODAL, "store": {"kind": "host-offload"}},
}


@pytest.fixture(scope="module")
def batches():
    data = mnist_like(200, seed=0)
    train, _ = data.split(0.9)
    ds = FederatedDataset(train, iid_partition(train.y, C))
    return lambda k: ds.stacked_batch(2, np.random.default_rng(k))


def _run(scheduler, fleet, **kw):
    exec_kw = {"topology": "ring", "tau1": 2, "tau2": 1, "alpha": 1,
               "learning_rate": 0.05, "latency": MNIST_LATENCY, **kw}
    return make_run(RunConfig(
        model=ModelSpec(instance=MnistCNN()), fleet=FleetSpec(**fleet),
        exec=ExecSpec(scheduler=scheduler, **exec_kw),
        num_clients=C, num_clusters=D, seed=0,
    ))


@pytest.mark.parametrize("fleet", list(FLEETS))
def test_sync_and_round_clocks_agree(batches, fleet):
    rounds = 4
    sync = _run("sync", FLEETS[fleet]).scheduler
    ipr = sync.cfg.tau1 * sync.cfg.tau2
    sync_dt = [sync.step(k, batches).dt for k in range(1, rounds * ipr + 1)]
    per_round = [sum(sync_dt[r * ipr:(r + 1) * ipr]) for r in range(rounds)]

    one = _run("round", FLEETS[fleet]).scheduler
    round_dt = [one.step(k, batches).dt for k in range(1, rounds + 1)]
    assert round_dt == pytest.approx(per_round, rel=1e-12)

    two = _run("round", FLEETS[fleet], rounds_per_step=2).scheduler
    super_dt = [two.step(k, batches).dt for k in (1, 2)]
    assert super_dt == pytest.approx(
        [round_dt[0] + round_dt[1], round_dt[2] + round_dt[3]], rel=1e-12)


def test_client_mask_composes_plan_and_faults():
    rt = _run("round", {"participation": UNIFORM_K, "faults": TRACE})
    rounds = rt.scheduler.rounds
    plan, faults = rounds.plan, rounds.faults
    for r in range(6):
        np.testing.assert_array_equal(
            rounds.client_mask(r), plan.mask(r) & faults.client_mask(r))
        # the async scheduler indexes the plan and the faults apart
        np.testing.assert_array_equal(
            rounds.client_mask(r, fault_r=r + 1),
            plan.mask(r) & faults.client_mask(r + 1))
    only_faults = _run("round", {"faults": TRACE}).scheduler.rounds
    np.testing.assert_array_equal(only_faults.client_mask(2),
                                  only_faults.faults.client_mask(2))
    plain = _run("round", {}).scheduler.rounds
    assert plain.client_mask(0).all()


def test_crashed_cluster_falls_back_to_its_m_hat_column():
    # both clients of cluster 0 crash in round 1 (their cluster cannot
    # aggregate nothing); client 5 crashes alone in cluster 2
    crash = [{"kind": "client-crash", "round": 1, "client": c, "until": 3}
             for c in (0, 1, 5)]
    sched = _run("sync", {"faults": crash}).scheduler
    rounds = sched.rounds
    m_hat = sched.cfg.clusters.m_hat()
    ops = rounds.operands(1)
    w = np.asarray(ops.weights, np.float64)
    assert not ops.pace[[0, 1, 5]].any()
    np.testing.assert_allclose(w[:2], m_hat[:2], rtol=1e-7)
    assert w[5] == 0.0 and w[4] == pytest.approx(1.0)
    assert len(ops.args) == 2  # weights and the round's (D, D) mixing
    assert np.asarray(ops.args[1]).shape == (D, D)


@pytest.mark.parametrize("scheduler", ["sync", "round"])
@pytest.mark.parametrize("fleet", ["uniform-k", "faults", "trace"])
def test_each_event_kind_priced_once_per_round(batches, monkeypatch,
                                               scheduler, fleet):
    calls = []
    price = FleetTiming.sync_event_time

    def counted(self, event, *a, **kw):
        calls.append((event, kw.get("t")))
        return price(self, event, *a, **kw)

    rt = _run(scheduler, FLEETS[fleet], tau2=2)  # local, intra and inter
    monkeypatch.setattr(FleetTiming, "sync_event_time", counted)
    rounds = 3
    steps = rounds * 4 if scheduler == "sync" else rounds
    for k in range(1, steps + 1):
        rt.scheduler.step(k, batches)
    assert len(calls) == 3 * rounds
    assert sorted(e for e, _ in calls) == sorted(
        ["local", "intra", "inter"] * rounds)


def test_static_round_step_called_with_three_arguments(batches):
    """``bench/train.py`` ``plant`` replaces ``_round_step`` after bind with a
    ``lambda p, s, b, *a``; the static path must call it with exactly the
    params, the optimizer state and the stacked batches."""
    seen = []
    for fleet, n_args in (({}, 3), ({"participation": UNIFORM_K}, 4),
                          ({"faults": TRACE}, 5)):
        sched = _run("round", fleet).scheduler
        step = sched._round_step

        def wrapper(*args, step=step):
            seen.append(args)
            return step(*args)

        sched._round_step = wrapper
        params, opt_state = sched.params, sched.opt_state
        sched.step(1, batches)
        args = seen.pop()
        assert len(args) == n_args
        assert args[0] is params and args[1] is opt_state
        assert set(args[2]) == {"x", "y"}
