"""Jit'd public wrappers for the bdeu_sweep Pallas kernels."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import (LANES, SUBLANES, instance_tile, pad_row,
                      resolve_interpret, round_up)
from .bdeu_sweep import delete_marginals_pallas, sweep_counts_pallas
from .ref import delete_marginals_ref, sweep_counts_ref


@partial(jax.jit, static_argnames=("max_q", "r_max", "tile_m", "interpret",
                                   "use_ref", "data_axis_name"))
def sweep_counts(
    cfg: jax.Array,
    child: jax.Array,
    data: jax.Array,
    *,
    max_q: int,
    r_max: int,
    tile_m: int | None = None,
    interpret: bool | None = None,
    use_ref: bool = False,
    data_axis_name: str | None = None,
) -> jax.Array:
    """(r_max, r_max, max_q, n) f32 joint sweep counts for one child.

    counts[b, a, j0, x] = #(child=b, X_x=a, base-config=j0) — every
    candidate family's contingency table for the FES sweep in one call.
    Pads m, n and max_q to the kernel's tile boundaries with
    counting-neutral sentinels (cfg = q_pad, child/data = r_max: all-zero
    one-hot rows/columns) and slices the padding back off; the kernel runs
    in interpret mode on the CPU and compiled on a TPU (``interpret=None``
    resolves per backend).

    ``data_axis_name``: inside shard_map with the instance axis sharded, each
    device contracts only its m/d one-hot shard; the joint counts are
    additive over instances, so one ``psum`` over that mesh axis rebuilds the
    global tables before the (m-independent) BDeu reduction.
    """
    m, n = data.shape
    if use_ref:
        counts = sweep_counts_ref(cfg, child, data, max_q=max_q, r_max=r_max)
    else:
        interpret = resolve_interpret(interpret)
        tm = instance_tile(m, tile_m)
        m_pad = round_up(m, tm)
        n_pad = round_up(max(n, 1), LANES)
        q_pad = round_up(max_q, SUBLANES)
        data_p = jnp.full((m_pad, n_pad), r_max, dtype=jnp.int32).at[
            :m, :n].set(data.astype(jnp.int32))
        counts = sweep_counts_pallas(
            pad_row(cfg, m_pad, q_pad), pad_row(child, m_pad, r_max), data_p,
            q_pad=q_pad, r_max=r_max, tile_m=tm, interpret=interpret)
        counts = counts[:, :, :max_q, :n]
    if data_axis_name is not None:
        counts = jax.lax.psum(counts, data_axis_name)
    return counts


@partial(jax.jit, static_argnames=("max_q", "r_max", "tile_m", "interpret",
                                   "use_ref", "data_axis_name"))
def sweep_counts_restricted(
    cfg: jax.Array,
    child: jax.Array,
    data: jax.Array,
    pids: jax.Array,
    *,
    max_q: int,
    r_max: int,
    tile_m: int | None = None,
    interpret: bool | None = None,
    use_ref: bool = False,
    data_axis_name: str | None = None,
) -> jax.Array:
    """(r_max, r_max, max_q, W) joint sweep counts over the W candidates in
    ``pids`` only — the restricted-E_i variant for the ring.

    The candidate data columns are gathered BEFORE the one-hot contraction,
    so the kernel's candidate axis (grid width, output block and flops) is
    W padded to the lane width, not n: a ring process with |E_i| ~ n/k
    allowed parents per column pays a W-wide contraction.

    This is the contraction behind BOTH restricted paths: the host-engine
    driver's per-column ``pids`` sweeps and the compiled ges_jit/shard_map
    ring, whose (n, W) pid_table matrix sweeps call it once per child from
    inside the while_loop (core/sweeps.sweep_matrix_restricted_body).
    """
    return sweep_counts(cfg, child, jnp.take(data, pids, axis=1),
                        max_q=max_q, r_max=r_max, tile_m=tile_m,
                        interpret=interpret, use_ref=use_ref,
                        data_axis_name=data_axis_name)


def slot_relabeling(slot_ar: jax.Array, slot_low: jax.Array,
                    q_pad: int) -> jax.Array:
    """(S, q_pad) int32 digit-sum map of each parent slot:
    t(j0) = (j0 // (low*ar)) * low + (j0 % low) sends row j0 of the family
    table to its row in the table with that slot summed out (identity for
    an ar = 1 slot).  t(j0) <= j0, so padded rows stay in range."""
    j0 = jnp.arange(q_pad, dtype=jnp.int32)[None, :]
    low = slot_low.astype(jnp.int32)[:, None]
    ar = slot_ar.astype(jnp.int32)[:, None]
    return (j0 // (low * ar)) * low + (j0 % low)


@partial(jax.jit, static_argnames=("max_q", "r_max", "tile_m", "interpret",
                                   "use_ref"))
def delete_marginals(
    cfg: jax.Array,
    child: jax.Array,
    slot_ar: jax.Array,
    slot_low: jax.Array,
    *,
    max_q: int,
    r_max: int,
    tile_m: int | None = None,
    interpret: bool | None = None,
    use_ref: bool = False,
) -> jax.Array:
    """(S + 1, max_q, r_max) f32 tables for the BES delete column of one
    child: [0] the current-family table, [s + 1] its marginal over parent
    slot s — all from ONE pass over the data, with the table VMEM-resident.

    cfg/child: (m,) int32 current-family radix codes and child values.
    slot_ar/slot_low: (S,) int32 per-slot arity and radix place value
    (1/1 = identity padding slot).  The caller reduces each table to its
    BDeu score (``bdeu.fused_delete_scores``).

    NOTE: the marginals are counts and additive over instances, but the
    kernel forms them from the *local* family table; under data sharding
    ``bdeu.fused_delete_scores`` routes to the two-step table-build + psum +
    marginalization path instead, so this op takes no ``data_axis_name``.
    """
    if use_ref:
        q_pad = round_up(max_q, LANES)
        return delete_marginals_ref(
            cfg, child, slot_relabeling(slot_ar, slot_low, q_pad)[:, :max_q],
            max_q=max_q, r_max=r_max)
    interpret = resolve_interpret(interpret)
    tm = instance_tile(cfg.shape[0], tile_m)
    m_pad = round_up(cfg.shape[0], tm)
    q_pad = round_up(max_q, LANES)
    r_pad = round_up(r_max, SUBLANES)
    tables = delete_marginals_pallas(
        pad_row(cfg, m_pad, q_pad), pad_row(child, m_pad, r_pad),
        slot_relabeling(slot_ar, slot_low, q_pad),
        r_pad=r_pad, tile_m=tm, interpret=interpret)
    return tables[:, :r_max, :max_q].transpose(0, 2, 1)
