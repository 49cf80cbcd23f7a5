"""Pure-jnp oracle for intra-cluster weighted aggregation."""
import jax
import jax.numpy as jnp


def cluster_agg_ref(w: jax.Array, weights: jax.Array, num_clusters: int) -> jax.Array:
    """(C, ...) -> (D, ...): weighted sums over contiguous C/D client groups."""
    g = w.shape[0] // num_clusters
    wf = w.astype(jnp.float32).reshape(num_clusters, g, -1)
    wt = weights.astype(jnp.float32).reshape(num_clusters, g)
    out = jnp.einsum("dgm,dg->dm", wf, wt).astype(w.dtype)
    return out.reshape((num_clusters,) + w.shape[1:])
