"""Per-kernel allclose tests: shape/dtype sweeps against the jnp oracles.

All Pallas kernels run in interpret=True (CPU container; TPU is the target).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClusterSpec, ring, star, fully_connected, mixing_matrix
from repro.kernels import (
    cluster_agg, cluster_agg_ref, cluster_agg_tree, flash_attention,
    flash_attention_ref, fused_transition, fused_transition_ref,
    fused_transition_tree, gossip_mix, gossip_mix_ref, gossip_mix_tree,
    normalized_update, sgd_update, sgd_update_tree,
)
from repro.kernels.fused_sgd import normalized_update_ref, sgd_update_ref

RNG = np.random.default_rng(0)


def arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# -- gossip_mix ----------------------------------------------------------------

@pytest.mark.parametrize("d,m,alpha", [(4, 512, 1), (8, 1024, 3), (16, 2048, 2), (6, 512, 5)])
@pytest.mark.parametrize("topo", [ring, fully_connected])
def test_gossip_mix_sweep(d, m, alpha, topo):
    y = arr((d, m))
    p = jnp.asarray(mixing_matrix(topo(d)), jnp.float32)
    out = gossip_mix(y, p, alpha=alpha, interpret=True, tile_m=256)
    ref = gossip_mix_ref(y, p, alpha)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_mix_dtypes(dtype):
    y = arr((8, 512), dtype)
    p = jnp.asarray(mixing_matrix(ring(8)), jnp.float32)
    out = gossip_mix(y, p, alpha=2, interpret=True)
    ref = gossip_mix_ref(y, p, 2)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol
    )


def test_gossip_mix_tree_pads_ragged_leaves():
    tree = {"a": arr((4, 3, 7)), "b": arr((4, 130))}
    p = jnp.asarray(mixing_matrix(ring(4)), jnp.float32)
    out = gossip_mix_tree(tree, p, alpha=1, interpret=True, tile_m=64)
    ref = {k: gossip_mix_ref(v.reshape(4, -1), p, 1).reshape(v.shape) for k, v in tree.items()}
    for k in tree:
        np.testing.assert_allclose(out[k], ref[k], atol=1e-5)


# -- cluster_agg -----------------------------------------------------------------

@pytest.mark.parametrize("c,d,m", [(8, 2, 512), (16, 4, 1024), (20, 5, 512), (12, 12, 256)])
def test_cluster_agg_sweep(c, d, m):
    w = arr((c, m))
    wt = jnp.asarray(RNG.uniform(0.1, 1.0, c), jnp.float32)
    out = cluster_agg(w, wt, d, interpret=True, tile_m=256)
    ref = cluster_agg_ref(w, wt, d)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cluster_agg_dtype(dtype):
    w = arr((8, 512), dtype)
    wt = jnp.asarray(np.full(8, 0.25), jnp.float32)
    out = cluster_agg(w, wt, 2, interpret=True)
    ref = cluster_agg_ref(w, wt, 2)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol)


# -- fused_transition ----------------------------------------------------------

def _factors(c, d, topo=ring):
    spec = ClusterSpec(
        c, tuple(i // (c // d) for i in range(c)),
        tuple(RNG.uniform(0.5, 2.0, c)),
    )
    vt = jnp.asarray(spec.V().T, jnp.float32)
    bt = jnp.asarray(spec.B().T, jnp.float32)
    p = jnp.asarray(mixing_matrix(topo(d), spec.m_tilde()), jnp.float32)
    return vt, p, bt


@pytest.mark.parametrize("c,d,m,alpha", [
    (8, 4, 512, 0),    # alpha=0: the V B (intra) event
    (8, 4, 512, 1),
    (16, 4, 1024, 2),
    (20, 5, 512, 3),
    (96, 8, 512, 1),   # C*D > VPU_MAX_TERMS: the MXU body
])
def test_fused_transition_sweep(c, d, m, alpha):
    vt, p, bt = _factors(c, d)
    w = arr((c, m))
    out = fused_transition(w, vt, p, bt, alpha=alpha, interpret=True, tile_m=256)
    ref = fused_transition_ref(w, vt, p, bt, alpha)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    # the fusion must equal the dense Lemma-1 einsum against T = V P^alpha B
    t = np.asarray(spec_t(vt, p, bt, alpha))
    np.testing.assert_allclose(out, np.einsum("cm,cd->dm", np.asarray(w), t), atol=1e-4)


def spec_t(vt, p, bt, alpha):
    v, b = np.asarray(vt).T, np.asarray(bt).T
    return v @ np.linalg.matrix_power(np.asarray(p), alpha) @ b


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_transition_dtypes(dtype):
    vt, p, bt = _factors(8, 4)
    w = arr((8, 512), dtype)
    out = fused_transition(w, vt, p, bt, alpha=2, interpret=True)
    ref = fused_transition_ref(w, vt, p, bt, 2)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol
    )


def test_fused_transition_tree_pads_ragged_leaves():
    vt, p, bt = _factors(8, 4)
    tree = {"a": arr((8, 3, 7)), "b": arr((8, 130))}
    out = fused_transition_tree(tree, vt, p, bt, alpha=1, interpret=True, tile_m=64)
    ref = {k: fused_transition_ref(v.reshape(8, -1), vt, p, bt, 1).reshape(v.shape)
           for k, v in tree.items()}
    for k in tree:
        np.testing.assert_allclose(out[k], ref[k], atol=1e-5)


# -- flash_attention ---------------------------------------------------------------

@pytest.mark.parametrize("b,s,hq,hkv,hd", [
    (1, 256, 4, 4, 64),    # MHA
    (2, 256, 8, 2, 64),    # GQA
    (1, 512, 4, 1, 128),   # MQA, larger hd
])
def test_flash_attention_shapes(b, s, hq, hkv, hd):
    q, k, v = arr((b, s, hq, hd)), arr((b, s, hkv, hd)), arr((b, s, hkv, hd))
    out = flash_attention(q, k, v, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("window,cap", [(None, None), (128, None), (None, 30.0), (192, 50.0)])
def test_flash_attention_window_softcap(window, cap):
    q, k, v = arr((2, 512, 4, 64)), arr((2, 512, 2, 64)), arr((2, 512, 2, 64))
    out = flash_attention(q, k, v, window=window, logit_cap=cap, interpret=True)
    ref = flash_attention_ref(q, k, v, window=window, logit_cap=cap)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_flash_attention_bf16():
    q, k, v = (arr((1, 256, 4, 64), jnp.bfloat16) for _ in range(3))
    out = flash_attention(q, k, v, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=3e-2
    )


def test_flash_attention_nonaligned_head_dim():
    """hd = 96 is padded to 128 with the scale compensated."""
    q, k, v = arr((1, 256, 2, 96)), arr((1, 256, 2, 96)), arr((1, 256, 2, 96))
    out = flash_attention(q, k, v, interpret=True)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5)


# -- fused_sgd -----------------------------------------------------------------------

@pytest.mark.parametrize("n,lr", [(1024, 0.1), (4096, 0.001)])
def test_sgd_update(n, lr):
    w, g = arr((n,)), arr((n,))
    np.testing.assert_allclose(
        sgd_update(w, g, lr, interpret=True), sgd_update_ref(w, g, lr), atol=1e-6
    )


def test_normalized_update_eq19():
    wf, w0 = arr((2048,)), arr((2048,))
    out = normalized_update(wf, w0, 1.0 / 7.0, interpret=True)
    np.testing.assert_allclose(out, normalized_update_ref(wf, w0, 1.0 / 7.0), atol=1e-6)


def test_sgd_update_tree_matches_plain():
    params = {"w": arr((3, 5, 7)), "b": arr((11,))}
    grads = {"w": arr((3, 5, 7)), "b": arr((11,))}
    out = sgd_update_tree(params, grads, 0.05, interpret=True, tile_m=64)
    ref = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
    for k in params:
        np.testing.assert_allclose(out[k], ref[k], atol=1e-6)


# -- partial edge blocks ------------------------------------------------------------

@pytest.mark.parametrize("kernel", ["fused_transition", "fused_transition_mxu",
                                    "gossip_mix", "cluster_agg", "sgd"])
def test_partial_edge_blocks(monkeypatch, kernel):
    """A leaf that no block divides, in rows or lanes, streams through partial
    edge blocks: a small VMEM budget forces a (8, 128) block onto a
    (C, 3, 37, 300) leaf, and the result still matches the oracle."""
    from repro.kernels import _tiling

    monkeypatch.setattr(_tiling, "_VMEM_BUDGET", 64 * 1024)
    c, d = (40, 4) if kernel == "fused_transition_mxu" else (8, 4)
    w = arr((c, 3, 37, 300))
    flat = w.reshape(c, -1)
    if kernel.startswith("fused_transition"):
        vt, p, bt = _factors(c, d)
        out = fused_transition(w, vt, p, bt, alpha=2, interpret=True, tile_m=128)
        ref = fused_transition_ref(flat, vt, p, bt, 2)
    elif kernel == "gossip_mix":
        p = jnp.asarray(mixing_matrix(ring(c)), jnp.float32)
        out = gossip_mix(w, p, alpha=2, interpret=True, tile_m=128)
        ref = gossip_mix_ref(flat, p, 2)
    elif kernel == "cluster_agg":
        wt = jnp.asarray(RNG.uniform(0.1, 1.0, c), jnp.float32)
        out = cluster_agg(w, wt, d, interpret=True, tile_m=128)
        ref = cluster_agg_ref(flat, wt, d)
    else:
        g = arr(w.shape)
        out = sgd_update(w, g, 0.1, interpret=True, tile_m=128)
        ref = sgd_update_ref(w, g, 0.1)
    assert out.shape[1:] == w.shape[1:]
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape), ref, atol=1e-5)


def test_reference_takes_the_kernel_leaf_shape():
    """One contract per entry point: ``impl="ref"`` takes (rows, ...) too."""
    c, d = 8, 4
    w = arr((c, 3, 5, 7))
    vt, p, bt = _factors(c, d)
    wt = jnp.asarray(RNG.uniform(0.1, 1.0, c), jnp.float32)
    pc = jnp.asarray(mixing_matrix(ring(c)), jnp.float32)
    pairs = [
        (fused_transition(w, vt, p, bt, alpha=1, impl="ref"),
         fused_transition(w, vt, p, bt, alpha=1, interpret=True)),
        (gossip_mix(w, pc, alpha=2, impl="ref"),
         gossip_mix(w, pc, alpha=2, interpret=True)),
        (cluster_agg(w, wt, d, impl="ref"),
         cluster_agg(w, wt, d, interpret=True)),
    ]
    for ref, out in pairs:
        assert ref.shape == out.shape
        np.testing.assert_allclose(out, ref, atol=1e-5)


# -- block sizing ---------------------------------------------------------------------

def test_block_shape_narrows_lanes_before_rows():
    """A fleet's block keeps one whole sublane tile of rows: the lanes give
    way first, down to 128, and the VMEM limit is raised for what is left."""
    from repro.kernels._tiling import _VMEM_BUDGET, block_shape

    c, d = 1000, 8
    per_pos = 2 * 2 * c * 4 + 4 * (2 * c + d)       # f32 transition, MXU body
    tn, tl, vmem = block_shape(4096, 14336, lane_tile=512, bytes_per_pos=per_pos,
                               itemsize=4, max_rows=8)
    assert (tn, tl) == (8, 128)
    assert vmem == per_pos * 8 * 128 > _VMEM_BUDGET
    # a handful of clients fills the budget with rows at the full lane tile
    tn, tl, vmem = block_shape(4096, 14336, lane_tile=512, bytes_per_pos=2 * 8 * 2 + 16,
                               itemsize=2)
    assert tl == 512 and tn % 16 == 0 and tn > 16 and vmem <= _VMEM_BUDGET


def test_block_shape_refuses_a_block_over_the_vmem_limit():
    from repro.kernels._tiling import block_shape

    with pytest.raises(ValueError, match="VMEM"):
        block_shape(320, 50, lane_tile=512, bytes_per_pos=24 * 10_000, itemsize=4)
