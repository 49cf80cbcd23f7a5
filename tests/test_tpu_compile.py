"""The main-path Pallas kernels compile for a described TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology that
is described, not attached.  Each test compiles one kernel on one
granite-8b parameter leaf through the tree helper the round step calls, and
checks that the kernel is in the program (``tpu_custom_call``) and that the
leaf streams in its own layout: in place, with no relayout copy of the leaf
(the temp buffer stays far below the leaf's size).

The topology is described only inside the module fixture below, never while
a module is imported: one process at a time may load the TPU library.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.backends import PallasBackend
from repro.core.protocol import ClusterSpec
from repro.kernels import sgd_update_tree

GRANITE = get_config("granite-8b")
C, D = 4, 2  # clients and clusters of the one-chip smoke
LEAVES = {
    "mlp": (1, GRANITE.d_model, GRANITE.d_ff),
    "attn_kv": (1, GRANITE.d_model, GRANITE.num_kv_heads * GRANITE.head_dim),
    "embed": (GRANITE.padded_vocab, GRANITE.d_model),
    "norm": (1, GRANITE.d_model),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the cache but cannot be read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _backend():
    return PallasBackend(ClusterSpec.uniform(C, D), np.full((D, D), 1.0 / D), 1,
                         interpret=False)


def _compile(fn, *leaves, in_place=True):
    compiled = jax.jit(fn, donate_argnums=0).lower(*leaves).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    leaf_bytes = leaves[0].size * leaves[0].dtype.itemsize
    if in_place:
        assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes / 100
    return compiled


def _leaf(rows, name, sharding):
    return jax.ShapeDtypeStruct((rows,) + LEAVES[name], jnp.bfloat16, sharding=sharding)


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_fused_transition_compiles(one_chip, name):
    backend = _backend()
    _compile(lambda w: backend.transition({"w": w}, "inter")["w"],
             _leaf(C, name, one_chip))


def test_fused_transition_compiles_for_a_fleet(one_chip):
    """1000 clients in 8 clusters, the dense anchor of the state-scaling lane
    (MnistCNN, f32): the MXU body, one sublane tile of rows per block and a
    raised scoped-VMEM limit.  XLA lays this leaf out with the client dim
    minor, so the (C, n, l) view costs a relayout copy: no in-place check."""
    c, d = 1000, 8
    backend = PallasBackend(ClusterSpec.uniform(c, d), np.full((d, d), 1.0 / d), 1,
                            interpret=False)
    w3 = jax.ShapeDtypeStruct((c, 320, 50), jnp.float32, sharding=one_chip)
    _compile(lambda w: backend.transition({"w": w}, "inter")["w"], w3, in_place=False)


def test_fused_sgd_compiles(one_chip):
    w = _leaf(C, "mlp", one_chip)
    _compile(lambda w, g: sgd_update_tree({"w": w}, {"w": g}, 0.01)["w"], w, w)


def test_gossip_mix_compiles(one_chip):
    backend = _backend()
    p = np.full((D, D), 1.0 / D)
    _compile(lambda y: backend.inter_cluster({"y": y}, p, 1)["y"],
             _leaf(D, "mlp", one_chip))


def test_leaf_shapes_are_granite_widths():
    """The leaves above are the real ones, not a guess at them."""
    from repro.models import CausalLM

    cfg = dataclasses.replace(GRANITE, num_layers=1)
    shapes = {jax.tree_util.keystr(p): s.shape for p, s in
              jax.tree_util.tree_leaves_with_path(
                  jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0)))}
    assert shapes["['blocks']['pos0']['ffn']['w_gate']"] == LEAVES["mlp"]
    assert shapes["['blocks']['pos0']['attn']['wk']"] == LEAVES["attn_kv"]
    assert shapes["['embed']"] == LEAVES["embed"]
    assert shapes["['blocks']['pos0']['ln_mix']"] == LEAVES["norm"]
    from repro.models import MnistCNN

    assert jax.eval_shape(MnistCNN().init, jax.random.PRNGKey(0))["w3"].shape == (320, 50)
