"""Pure-jnp oracle for the bdeu_count kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def contingency_counts_ref(
    cfg: jax.Array, child: jax.Array, *, max_q: int, r_pad: int
) -> jax.Array:
    """Dense (max_q, r_pad) contingency counts; rows whose cfg or child is
    out of range (padding sentinels) are ignored."""
    valid = (cfg >= 0) & (cfg < max_q) & (child >= 0) & (child < r_pad)
    flat = jnp.where(valid, cfg * r_pad + child, 0)
    counts = jax.ops.segment_sum(
        valid.astype(jnp.float32), flat, num_segments=max_q * r_pad
    )
    return counts.reshape(max_q, r_pad)
