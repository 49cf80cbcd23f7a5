"""The differentiable Pallas flash-attention kernel against the XLA path.

The kernel (``repro.kernels.flash_attention``, run here in interpret mode)
and ``blocked_causal_attention`` compute the same causal GQA attention:
forward and the gradients for q, k and v agree, vmapped over a client axis
as the local update runs them.  In float32 they agree to float32 rounding
(readings below 1e-6); in bfloat16 the kernel takes bf16 operands into its
matmuls where the XLA path computes in float32, so they agree to bf16
rounding (readings 2e-3 to 4e-3).  A model-level case runs ``CausalLM.loss``
gradients through the kernel forced into the model against the XLA path.

    JAX_PLATFORMS=cpu python3 -m pytest -q tests/test_flash_attention.py
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import block_size, flash_attention
from repro.models import CausalLM, transformer
from repro.models.config import ArchConfig
from repro.models.layers import blocked_causal_attention

CLIENTS, HQ, HKV, HD = 2, 8, 2, 128
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 1e-2}  # relative norm of the difference


def rel(a, b) -> float:
    a, b = (jnp.asarray(x, jnp.float32).ravel() for x in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def value_and_grads(attend, q, k, v, w):
    """A weighted sum of the client-vmapped attention, and its gradients."""
    def f(q, k, v):
        return jnp.sum(jax.vmap(attend)(q, k, v).astype(jnp.float32) * w)

    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("window,cap", [(None, None), (128, None), (None, 30.0)],
                         ids=["causal", "window128", "softcap30"])
@pytest.mark.parametrize("s,dtype", [(256, jnp.float32), (256, jnp.bfloat16),
                                     (1024, jnp.float32)],  # 2x2 blocks of 512
                         ids=["s256-f32", "s256-bf16", "s1024-f32"])
def test_kernel_matches_blocked_attention(s, dtype, window, cap):
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.normal(size=(CLIENTS, 1, s, HQ, HD)), dtype)
    k = jnp.asarray(rng.normal(size=(CLIENTS, 1, s, HKV, HD)), dtype)
    v = jnp.asarray(rng.normal(size=(CLIENTS, 1, s, HKV, HD)), dtype)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    kernel = functools.partial(flash_attention, window=window, logit_cap=cap, interpret=True)
    xla = functools.partial(blocked_causal_attention, window=window, logit_cap=cap, chunk=128)

    out = jax.vmap(kernel)(q, k, v)
    ref = jax.vmap(xla)(q, k, v)
    assert out.dtype == ref.dtype == dtype
    assert rel(out, ref) < TOL[dtype]
    _, grads = value_and_grads(kernel, q, k, v, w)
    _, ref_grads = value_and_grads(xla, q, k, v, w)
    for name, g, r in zip("qkv", grads, ref_grads):
        assert g.dtype == dtype, name
        assert rel(g, r) < TOL[dtype], name


def test_block_size_tiles_the_sequence():
    assert [block_size(s) for s in (128, 256, 512, 640, 1024, 4096)] == [
        128, 256, 512, 128, 512, 512]
    assert block_size(64) is None and block_size(200) is None


def test_off_a_tpu_the_model_keeps_the_xla_path():
    assert jax.default_backend() != "tpu"
    assert transformer._flash_kernel(1024) is None


def test_model_loss_gradients_through_the_kernel(monkeypatch):
    """``CausalLM.loss`` and its gradients, remat on, with the kernel forced
    into the model (interpret mode), against the XLA path."""
    cfg = ArchConfig(name="flash-lm", family="dense", num_layers=2, d_model=128, d_ff=256,
                     vocab_size=256, num_heads=4, num_kv_heads=2, head_dim=32,
                     dtype="float32", remat=True, attn_chunk=128)
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    grad = lambda: jax.jit(jax.value_and_grad(model.loss))(params, batch)  # noqa: E731

    ref_loss, ref_grads = grad()
    forced = functools.partial(flash_attention, interpret=True)
    monkeypatch.setattr(transformer, "_flash_kernel", lambda s: forced)
    loss, grads = grad()
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        r = ref_grads
        for key in path:
            r = r[key.key]
        assert rel(g, r) < 1e-4, jax.tree_util.keystr(path)
