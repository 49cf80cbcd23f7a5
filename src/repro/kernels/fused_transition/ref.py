"""Pure-jnp oracle for the fused Lemma-1 transition kernel."""
import jax
import jax.numpy as jnp


def fused_transition_ref(w: jax.Array, vt: jax.Array, p: jax.Array,
                         bt: jax.Array, alpha: int = 1) -> jax.Array:
    """B^T (P^T)^alpha V^T W  — i.e. (W^T (V P^alpha B))^T on a (C, ...) leaf."""
    y = vt.astype(jnp.float32) @ w.reshape(w.shape[0], -1).astype(jnp.float32)
    pf = p.astype(jnp.float32)
    for _ in range(alpha):
        y = pf.T @ y
    return (bt.astype(jnp.float32) @ y).astype(w.dtype).reshape(w.shape)
