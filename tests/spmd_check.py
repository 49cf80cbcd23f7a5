"""Multi-device SPMD checks, run as a subprocess with forced host devices.

Invoked by test_spmd.py:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python tests/spmd_check.py <check>

Checks:
  gossip_equivalence — structured ppermute aggregation == dense Lemma-1 einsum
  tiny_dryrun        — lower+compile train/prefill/serve on a 4x2 test mesh
  decode_sharded     — sequence-sharded LSE-merge decode == local decode
  lm_collective_mesh — LM round: shard_map collective on a client mesh ==
                       the single-device vmap emulation (auto param_specs)
  continuous_mesh_serving — slot-pool decode with the replica stack sharded
                       across a cluster mesh == the off-mesh vmap fallback
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def check_gossip_equivalence():
    from repro import optim
    from repro.core import FLSpec, build_fl_train_step, init_stacked
    from repro.launch.mesh import make_test_mesh
    from repro.models import MnistCNN
    from repro.sharding import MeshAxes

    mesh = make_test_mesh(data=8, model=1)
    model = MnistCNN()
    fl = dict(num_clients=8, num_clusters=4, tau1=1, tau2=1, alpha=2, learning_rate=0.05)
    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(8, 4, 28, 28, 1)), jnp.float32),
        "y": jnp.asarray(rng.integers(0, 10, (8, 4)), jnp.int32),
    }
    params = init_stacked(model, 8, jax.random.PRNGKey(0))
    pspecs = jax.tree.map(lambda x: P("data", *([None] * (x.ndim - 1))), params)

    with mesh:
        dense_step = jax.jit(build_fl_train_step(
            model, optim.sgd(0.05), FLSpec(**fl, impl="dense"), event="inter"))
        p_dense, _, _ = dense_step(params, (), batch)

        gossip_step = jax.jit(
            build_fl_train_step(
                model, optim.sgd(0.05), FLSpec(**fl, impl="gossip"),
                event="inter", mesh=mesh, param_specs=pspecs,
            ),
            in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
                          (), None),
        )
        p_gossip, _, _ = gossip_step(params, (), batch)

    for a, b in zip(jax.tree.leaves(p_dense), jax.tree.leaves(p_gossip)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    print("gossip_equivalence OK")


def check_tiny_dryrun():
    import dataclasses
    from repro.configs import get_config
    from repro.configs.shapes import InputShape
    from repro.launch.mesh import make_test_mesh
    from repro.launch.steps import build_prefill, build_serve, build_train
    from repro.core.sdfeel import FLSpec
    from repro.roofline import roofline_terms

    mesh = make_test_mesh(data=4, model=2)
    cfg = dataclasses.replace(
        get_config("qwen2.5-3b").reduced(), num_heads=4, num_kv_heads=2, head_dim=64
    )
    shp_train = InputShape("t", 128, 8, "train")
    shp_pref = InputShape("p", 128, 4, "prefill")
    shp_dec = InputShape("d", 128, 8, "decode")
    with mesh:
        fl = FLSpec(num_clients=4, num_clusters=2, tau1=1, tau2=1, alpha=1)
        jt, at = build_train(cfg, shp_train, mesh, fl=fl)
        ct = jt.lower(*at).compile()
        assert ct.memory_analysis() is not None
        terms = roofline_terms(ct)
        assert terms.flops_per_device > 0
        jp, ap = build_prefill(cfg, shp_pref, mesh)
        jp.lower(*ap).compile()
        js, as_ = build_serve(cfg, shp_dec, mesh)
        cs = js.lower(*as_).compile()
        assert "all" in str(sorted(terms.per_kind)) or terms.collective_ops >= 0
    print("tiny_dryrun OK")


def check_decode_sharded():
    import dataclasses
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models import CausalLM
    from repro.sharding import make_decode_impl

    mesh = make_test_mesh(data=4, model=2)
    cfg = dataclasses.replace(
        get_config("granite-8b").reduced(), num_heads=4, num_kv_heads=2,
        head_dim=64, dtype="float32",
    )
    model_local = CausalLM(cfg)
    params = model_local.init(jax.random.PRNGKey(0))
    b, sc = 8, 64
    cache = model_local.init_cache(b, sc)
    rng = np.random.default_rng(1)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (b,)), jnp.int32)

    # prefill a few tokens through local decode to make the cache non-trivial
    step_local = jax.jit(model_local.decode_step)
    c_l = cache
    for t in range(4):
        logits_l, c_l = step_local(params, tok, c_l, jnp.int32(t))

    impl = make_decode_impl(mesh, seq_axes=("model",), batch_axes=("data",),
                            gather_heads=False, model_axis="model")
    model_sh = CausalLM(cfg, decode_impl=impl)
    with mesh:
        step_sh = jax.jit(model_sh.decode_step)
        c_s = cache
        for t in range(4):
            logits_s, c_s = step_sh(params, tok, c_s, jnp.int32(t))
    np.testing.assert_allclose(
        np.asarray(logits_l), np.asarray(logits_s), atol=2e-4
    )
    print("decode_sharded OK")


def check_lm_collective_mesh():
    """Federated-LM round on a real client mesh == the vmap emulation.

    The collective backend bound to a one-client-per-device mesh runs its
    hypercube + ring transitions under shard_map with *derived* param_specs
    (every stacked leaf sharded on the leading clients axis — the layout the
    batched local-update stage pins).  The same round without a mesh runs
    the single-device vmap emulation; trajectories must agree.
    """
    from repro import optim
    from repro.core import FLSpec, init_stacked
    from repro.core.backends import resolve_backend
    from repro.core.round_engine import build_fl_round_step
    from repro.data import FederatedLM
    from repro.launch.mesh import make_client_mesh
    from repro.models import CausalLM
    from repro.models.config import ArchConfig

    C, SEQ, B = 8, 16, 2
    cfg = ArchConfig(
        name="spmd-lm", family="dense", num_layers=2, d_model=32, d_ff=64,
        vocab_size=128, num_heads=2, num_kv_heads=1, head_dim=16,
        dtype="float32", remat=False, attn_chunk=SEQ, tie_embeddings=True,
    )
    model = CausalLM(cfg)
    fl = FLSpec(num_clients=C, num_clusters=4, tau1=2, tau2=1, alpha=1,
                learning_rate=0.1, topology="ring")
    proto = fl.protocol()
    opt = optim.sgd(fl.learning_rate)

    ds = FederatedLM.generate(C, 64, SEQ, 128, seed=0)
    rng = np.random.default_rng(0)
    draws = [ds.stacked_batch(B, rng) for _ in range(fl.tau1 * fl.tau2)]
    window = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *draws)
    params0 = init_stacked(model, C, jax.random.PRNGKey(0))

    # emulation: no mesh -> jitted vmapped per-client transition
    emu_backend = resolve_backend("collective", proto.clusters, proto.P(),
                                  fl.alpha)
    assert getattr(emu_backend, "mesh", None) is None
    step_emu = jax.jit(build_fl_round_step(model, opt, fl, backend=emu_backend))
    p_emu = params0
    for _ in range(2):
        p_emu, _, losses_emu = step_emu(p_emu, (), window)

    # shard_map: one client per device, param_specs derived by the backend
    mesh = make_client_mesh(C)
    mesh_backend = resolve_backend("collective", proto.clusters, proto.P(),
                                   fl.alpha, mesh=mesh)
    assert mesh_backend.mesh is mesh and mesh_backend.param_specs is None
    with mesh:
        step_mesh = jax.jit(
            build_fl_round_step(model, opt, fl, backend=mesh_backend)
        )
        p_mesh = params0
        for _ in range(2):
            p_mesh, _, losses_mesh = step_mesh(p_mesh, (), window)

    np.testing.assert_allclose(
        np.asarray(losses_emu), np.asarray(losses_mesh), atol=1e-6
    )
    for a, b in zip(jax.tree.leaves(p_emu), jax.tree.leaves(p_mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    print("lm_collective_mesh OK")


def check_round_scheduler_mesh():
    """``make_run`` with ``mesh="auto"`` places the client state one client
    per device before the first step, so the round step compiles once and
    every step keeps that layout."""
    from repro.core.config import ExecSpec, ModelSpec, RunConfig
    from repro.core.runtime import make_run
    from repro.data import FederatedLM
    from repro.models import CausalLM
    from repro.models.config import ArchConfig

    C = 4
    cfg = ArchConfig(
        name="spmd-round", family="dense", num_layers=1, d_model=32, d_ff=64,
        vocab_size=128, num_heads=2, num_kv_heads=1, head_dim=16,
        dtype="float32", remat=False, attn_chunk=16, tie_embeddings=True,
    )
    ds = FederatedLM.generate_clustered(C, 8, 16, 128, C, seed=0)
    runtime = make_run(RunConfig(
        model=ModelSpec(instance=CausalLM(cfg)),
        exec=ExecSpec(scheduler="round", backend="auto", tau1=2, tau2=1,
                      alpha=1, mesh="auto"),
        num_clients=C, num_clusters=C,
    ))
    sched = runtime.scheduler
    assert sched.backend.name == "collective" and sched.mesh is not None

    def one_per_device(tree):
        return all(len(x.addressable_shards) == C
                   and all(s.data.shape[0] == 1 for s in x.addressable_shards)
                   for x in jax.tree.leaves(tree))

    assert one_per_device(sched.params)
    for _ in range(3):
        ev = runtime.step(lambda k: ds.stacked_batch(1, np.random.default_rng(k)))
        assert np.all(np.isfinite(np.asarray(ev.losses)))
    assert sched._round_step._cache_size() == 1, sched._round_step._cache_size()
    assert one_per_device(sched.params)
    print("round_scheduler_mesh OK")


def check_continuous_mesh_serving():
    """Continuous serving with mesh-sharded replicas == the vmap fallback.

    The stacked ``(D, ...)`` cluster replicas are device_put across a
    4-device cluster mesh; slot admission, chunked decode, and harvest run
    the same jitted programs as the off-mesh path, so every request's
    greedy continuation must be bitwise identical.
    """
    import dataclasses
    from repro.launch.mesh import make_cluster_mesh
    from repro.models import CausalLM
    from repro.models.config import ArchConfig
    from repro.serving import ContinuousFederatedServer, Request

    D = 4
    cfg = ArchConfig(
        name="spmd-serve", family="dense", num_layers=2, d_model=32, d_ff=64,
        vocab_size=64, num_heads=2, num_kv_heads=1, head_dim=16,
        dtype="float32", remat=False, attn_chunk=16, tie_embeddings=True,
    )
    model = CausalLM(cfg)
    stack = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[model.init(jax.random.PRNGKey(s)) for s in range(D)],
    )
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, int(rng.integers(3, 17))),
            max_new_tokens=int(rng.integers(1, 9)),
            eos_id=int(rng.integers(0, cfg.vocab_size)),
            cluster_id=int(rng.integers(0, D)),
        )
        for i in range(12)
    ]

    def serve(mesh):
        srv = ContinuousFederatedServer(
            model, stack, mesh=mesh, max_batch=4, length_buckets=(8, 16),
            gen_cap=8, chunk_steps=3,
        )
        batch = [dataclasses.replace(r, output=None) for r in reqs]
        for r in batch:
            srv.submit(r)
        srv.run()
        # no admission pattern or state placement recompiles a program
        assert srv.compile_counts() == {"prefill": 2, "admit": 2, "decode": 1}, (
            srv.compile_counts())
        return batch

    mesh = make_cluster_mesh(D)
    assert mesh.devices.size == D
    on = serve(mesh)
    off = serve(None)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(np.asarray(a.output),
                                      np.asarray(b.output))
    print("continuous_mesh_serving OK")


if __name__ == "__main__":
    {
        "gossip_equivalence": check_gossip_equivalence,
        "tiny_dryrun": check_tiny_dryrun,
        "decode_sharded": check_decode_sharded,
        "lm_collective_mesh": check_lm_collective_mesh,
        "continuous_mesh_serving": check_continuous_mesh_serving,
        "round_scheduler_mesh": check_round_scheduler_mesh,
    }[sys.argv[1]]()
