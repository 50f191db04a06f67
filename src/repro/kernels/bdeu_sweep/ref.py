"""Pure-jnp oracles for the bdeu_sweep kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sweep_counts_ref(
    cfg: jax.Array, child: jax.Array, data: jax.Array, *, max_q: int, r_max: int
) -> jax.Array:
    """(r_max, r_max, max_q, n) joint counts [b, a, j0, x]; rows whose cfg
    or child is out of range are ignored.

    counts[b, a, j0, x] = #(child=b, X_x=a, cfg0=j0), via one segment-sum of
    the value-major (m, r_max*n) data one-hot over the joint (b, j0) index.
    """
    m, n = data.shape
    oh_all = jax.nn.one_hot(data, r_max, dtype=jnp.float32)      # (m, n, r)
    oh_all = oh_all.transpose(0, 2, 1).reshape(m, r_max * n)
    valid = (cfg >= 0) & (cfg < max_q) & (child >= 0) & (child < r_max)
    idx = jnp.where(valid, child * max_q + cfg, r_max * max_q)
    counts = jax.ops.segment_sum(oh_all, idx,
                                 num_segments=r_max * max_q + 1)
    counts = counts[:r_max * max_q].reshape(r_max, max_q, r_max, n)
    return counts.transpose(0, 2, 1, 3)


def delete_marginals_ref(
    cfg: jax.Array,
    child: jax.Array,
    tmap: jax.Array,
    *,
    max_q: int,
    r_max: int,
) -> jax.Array:
    """(S + 1, max_q, r_max) tables; the jnp oracle for
    ``delete_marginals_pallas`` (same contract, segment-sum realization).

    Builds the ONE current-family (max_q, r_max) table (out-of-range rows
    ignored), then sums it per parent slot s under the digit-sum relabeling
    ``tmap[s]`` (ops.slot_relabeling).
    """
    valid = (cfg >= 0) & (cfg < max_q) & (child >= 0) & (child < r_max)
    flat = jnp.where(valid, cfg * r_max + child, max_q * r_max)
    counts = jax.ops.segment_sum(
        jnp.ones_like(flat, dtype=jnp.float32), flat,
        num_segments=max_q * r_max + 1)[:max_q * r_max]
    counts = counts.reshape(max_q, r_max)
    marginals = [jax.ops.segment_sum(counts, t, num_segments=max_q)
                 for t in tmap]
    return jnp.stack([counts] + marginals)
