"""The main-path Pallas kernels compile for a described TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology that
is described, not attached.  Each test compiles one kernel on one
granite-8b parameter leaf through the tree helper the round step calls, and
checks that the kernel is in the program (``tpu_custom_call``) and that the
leaf streams in its own layout: in place, with no relayout copy of the leaf
(the temp buffer stays far below the leaf's size).  The loss head's
training step compiles with no float32 copy of the vocabulary-wide logits.

The topology is described only inside the ``described_chip`` fixture
(``tests/conftest.py``), never while a module is imported: one process at
a time may load the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
def test_fused_transition_compiles(described_chip, name):
    backend = _backend()
    _compile(lambda w: backend.transition({"w": w}, "inter")["w"],
             _leaf(C, name, described_chip))


def test_fused_transition_compiles_for_a_fleet(described_chip):
    """1000 clients in 8 clusters, the dense anchor of the state-scaling lane
    (MnistCNN, f32): the MXU body, one sublane tile of rows per block and a
    raised scoped-VMEM limit.  XLA lays this leaf out with the client dim
    minor, so the (C, n, l) view costs a relayout copy: no in-place check."""
    c, d = 1000, 8
    backend = PallasBackend(ClusterSpec.uniform(c, d), np.full((d, d), 1.0 / d), 1,
                            interpret=False)
    w3 = jax.ShapeDtypeStruct((c, 320, 50), jnp.float32, sharding=described_chip)
    _compile(lambda w: backend.transition({"w": w}, "inter")["w"], w3, in_place=False)


def test_fused_sgd_compiles(described_chip):
    w = _leaf(C, "mlp", described_chip)
    _compile(lambda w, g: sgd_update_tree({"w": w}, {"w": g}, 0.01)["w"], w, w)


def test_gossip_mix_compiles(described_chip):
    backend = _backend()
    p = np.full((D, D), 1.0 / D)
    _compile(lambda y: backend.inter_cluster({"y": y}, p, 1)["y"],
             _leaf(D, "mlp", described_chip))


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


def _old_head_loss(model):
    """``CausalLM.loss`` as it was: log-softmax and ``take_along_axis`` over
    a float32 copy of the logits."""
    from repro import spans

    def loss(params, batch):
        logits, aux = model.forward(params, batch)
        with jax.named_scope(spans.LM_HEAD):
            logp = jax.nn.log_softmax(logits[..., :model.cfg.vocab_size], axis=-1)
            nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        return nll.mean() + model.cfg.router_aux_coef * aux
    return loss


def _vocab_f32_buffers(text, vocab):
    """Top-level instructions of the entry computation with a float32 result
    whose last dimension is the vocabulary."""
    entry = text[text.index("\nENTRY"):]
    return [line for line in entry.splitlines()
            if re.search(rf"f32\[[\d,]*\b{vocab}\]", line.split(" metadata=")[0].split("(%")[0])]


def _lm_head_scatters(text):
    return [line for line in text.splitlines()
            if "scatter" in line and "sdfeel.lm_head" in line]


def test_loss_head_keeps_no_float32_logits(described_chip):
    """The vmapped ``value_and_grad`` of ``CausalLM.loss`` (4 clients, S 1024,
    vocabulary 49152, bf16) holds no float32 vocabulary-wide buffer and no
    scatter in the loss head, and needs less temp than the log-softmax form
    it replaced, which shows both."""
    from repro.models import CausalLM

    cfg = dataclasses.replace(GRANITE.reduced(), num_layers=1, vocab_size=GRANITE.vocab_size,
                              dtype="bfloat16", remat=True, attn_chunk=512)
    model = CausalLM(cfg)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((C,) + a.shape, a.dtype, sharding=described_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((C, 1, 1024), jnp.int32, sharding=described_chip)
             for k in ("tokens", "labels")}

    def compile_step(loss):
        return jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(params, batch).compile()

    new, old = compile_step(model.loss), compile_step(_old_head_loss(model))
    v = cfg.padded_vocab
    assert _vocab_f32_buffers(old.as_text(), v) and _lm_head_scatters(old.as_text())
    assert not _vocab_f32_buffers(new.as_text(), v)
    assert not _lm_head_scatters(new.as_text())
    assert (new.memory_analysis().temp_size_in_bytes
            < old.memory_analysis().temp_size_in_bytes)
