"""``layers.softmax_cross_entropy`` against the log-softmax form it replaced.

The loss head computes the NLL from the head's own-dtype logits with its own
backward.  Each case checks the value and the gradient against
``log_softmax`` + ``take_along_axis`` over a float32 copy of the logits,
that the gradient comes back in the logits' dtype, and that the padded
vocabulary columns get no gradient.  ``CausalLM.loss`` of every smoke
configuration is checked against the same form built from ``forward``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models import CausalLM
from repro.models.layers import softcap, softmax_cross_entropy
from test_models_smoke import make_batch


def reference_nll(logits, labels, vocab, cap):
    logp = jax.nn.log_softmax(softcap(logits.astype(jnp.float32), cap)[..., :vocab], axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def _inputs(seed, shape, width, vocab, dtype, scale=4.0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=shape + (width,)) * scale, dtype)
    labels = jnp.asarray(rng.integers(0, vocab, shape), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.5, 1.5, shape), jnp.float32)
    return logits, labels, weights


def _weighted(nll_fn):
    return lambda logits, labels, w: jnp.sum(nll_fn(logits, labels) * w) / w.size


def _masked(nll_fn, frontend):
    def loss(logits, labels, _):
        nll = nll_fn(logits, labels)
        mask = jnp.ones(nll.shape, jnp.float32).at[..., :frontend].set(0.0)
        return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
    return loss


def _audio(nll_fn):
    # logits (B,S,K,V) against labels (B,K,S), as CausalLM.loss lays them out
    return lambda logits, labels, w: jnp.mean(nll_fn(logits.transpose(0, 2, 1, 3), labels) * w)


# name: (leading shape, width, vocab, dtype, softcap, loss builder, vmapped)
CASES = {
    "f32": ((2, 16), 384, 384, jnp.float32, None, _weighted, False),
    "bf16": ((2, 16), 384, 384, jnp.bfloat16, None, _weighted, False),
    "padded_vocab": ((2, 16), 512, 300, jnp.bfloat16, None, _weighted, False),
    "softcap30": ((2, 16), 384, 384, jnp.float32, 30.0, _weighted, False),
    "softcap30_padded_bf16": ((2, 16), 512, 300, jnp.bfloat16, 30.0, _weighted, False),
    "frontend_mask": ((2, 16), 384, 384, jnp.bfloat16, None,
                      lambda f: _masked(f, 5), False),
    "audio_codebooks": ((2, 16, 3), 256, 200, jnp.bfloat16, None, _audio, False),
    "vmap_clients": ((4, 2, 16), 512, 300, jnp.bfloat16, None, _weighted, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_matches_log_softmax_form(case):
    shape, width, vocab, dtype, cap, build, vmapped = CASES[case]
    logits, labels, weights = _inputs(sum(map(ord, case)), shape, width, vocab, dtype)
    if build is _audio:  # labels (B,K,S) for logits (B,S,K,V)
        labels = labels.transpose(0, 2, 1)
        weights = weights.transpose(0, 2, 1)
    new = build(lambda l, y: softmax_cross_entropy(l, y, vocab=vocab, softcap=cap))
    old = build(lambda l, y: reference_nll(l, y, vocab, cap))
    grad_new = jax.value_and_grad(new)
    grad_old = jax.value_and_grad(old)
    if vmapped:  # one client per leading row, as core/local_update.py vmaps
        grad_new, grad_old = jax.vmap(grad_new), jax.vmap(grad_old)
    v_new, g_new = jax.jit(grad_new)(logits, labels, weights)
    v_old, g_old = jax.jit(grad_old)(logits, labels, weights)

    np.testing.assert_allclose(np.asarray(v_new), np.asarray(v_old), rtol=1e-5)
    assert g_new.dtype == logits.dtype and g_new.shape == logits.shape
    g_new = np.asarray(g_new.astype(jnp.float32))
    g_old = np.asarray(g_old.astype(jnp.float32))
    # float32: a few roundings apart; bfloat16: one rounding to the dtype apart
    rtol = 1e-5 if dtype == jnp.float32 else float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(g_new, g_old, rtol=rtol, atol=rtol * np.abs(g_old).max() * 1e-2)
    assert not np.any(g_new[..., vocab:])
    assert np.all(np.isfinite(g_new))


def _old_loss(model):
    """``CausalLM.loss`` as it was: log-softmax over the float32 logits."""
    cfg = model.cfg

    def loss(params, batch):
        logits, aux = model.forward(params, batch)
        if cfg.modality == "audio" and cfg.num_codebooks > 1:
            logits = logits.transpose(0, 2, 1, 3)
        logp = jax.nn.log_softmax(logits[..., :cfg.vocab_size], axis=-1)
        nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        if cfg.frontend_tokens:
            mask = jnp.ones(nll.shape, jnp.float32).at[..., :cfg.frontend_tokens].set(0.0)
            return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0) + cfg.router_aux_coef * aux
        return nll.mean() + cfg.router_aux_coef * aux
    return loss


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_causal_lm_loss_matches_old_formula(name):
    cfg = get_config(name).reduced()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = make_batch(cfg, b=1, s=32, seed=3)
    # one program: the trunk both losses share is compiled once
    both = jax.jit(lambda p, b: (jax.value_and_grad(model.loss)(p, b),
                                 jax.value_and_grad(_old_loss(model))(p, b)))
    (v_new, g_new), (v_old, g_old) = both(params, batch)
    np.testing.assert_allclose(float(v_new), float(v_old), rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_new), jax.tree.leaves(g_old)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b), jax.tree_util.keystr(path)
