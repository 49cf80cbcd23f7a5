"""Pure-jnp oracle for the gossip mixing kernel."""
import jax
import jax.numpy as jnp


def gossip_mix_ref(y: jax.Array, p: jax.Array, alpha: int = 1) -> jax.Array:
    """Y @ P^alpha with column convention new[d] = sum_j p[j, d] y[j]."""
    out = y.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    for _ in range(alpha):
        out = jnp.einsum("j...,jd->d...", out, pf)
    return out.astype(y.dtype)
