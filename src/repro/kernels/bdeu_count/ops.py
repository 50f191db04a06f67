"""Jit'd public wrapper for the bdeu_count Pallas kernel."""
from __future__ import annotations

from functools import partial

import jax

from ..common import (LANES, SUBLANES, instance_tile, pad_row,
                      resolve_interpret, round_up)
from .bdeu_count import contingency_counts_pallas
from .ref import contingency_counts_ref


@partial(jax.jit, static_argnames=("max_q", "r_max", "tile_m", "interpret",
                                   "use_ref", "data_axis_name"))
def contingency_counts(
    cfg: jax.Array,
    child: jax.Array,
    *,
    max_q: int,
    r_max: int,
    tile_m: int | None = None,
    interpret: bool | None = None,
    use_ref: bool = False,
    data_axis_name: str | None = None,
) -> jax.Array:
    """(max_q, r_max) f32 contingency table for one (parent-config, child) pair.

    Pads m to a tile multiple and the table to the kernel's (r_pad, q_pad)
    sublane/lane boundaries with counting-neutral sentinels (cfg = q_pad,
    child = r_pad), then slices and transposes the padding back off.  The
    kernel runs in interpret mode on the CPU and compiled on a TPU
    (``interpret=None`` resolves per backend, see common.resolve_interpret).

    ``data_axis_name``: inside shard_map with the instance axis sharded, each
    device counts only its m/d shard; contingency tables are additive over
    instances, so one ``psum`` over that mesh axis reconstructs the global
    table before the (m-independent) BDeu reduction.
    """
    if use_ref:
        counts = contingency_counts_ref(cfg, child, max_q=max_q, r_pad=r_max)
    else:
        interpret = resolve_interpret(interpret)
        tm = instance_tile(cfg.shape[0], tile_m)
        m_pad = round_up(cfg.shape[0], tm)
        q_pad = round_up(max_q, LANES)
        r_pad = round_up(r_max, SUBLANES)
        counts_t = contingency_counts_pallas(
            pad_row(cfg, m_pad, q_pad), pad_row(child, m_pad, r_pad),
            q_pad=q_pad, r_pad=r_pad, tile_m=tm, interpret=interpret)
        counts = counts_t[:r_max, :max_q].T
    if data_axis_name is not None:
        counts = jax.lax.psum(counts, data_axis_name)
    return counts
