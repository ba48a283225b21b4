"""Pure-jnp oracle for the segment SpMM kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_spmm_ref(x: jax.Array, edges: jax.Array, edge_weights: jax.Array,
                     num_nodes: int) -> jax.Array:
    """End-to-end oracle: A_tilde @ x via plain gather + segment_sum."""
    msgs = jnp.take(x, edges[:, 0], axis=0) \
        * edge_weights[:, None].astype(x.dtype)
    return jax.ops.segment_sum(msgs, edges[:, 1], num_segments=num_nodes)
