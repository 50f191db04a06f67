"""Pallas TPU kernel: contingency-table accumulation for BDeu scoring.

The GPU-idiomatic implementation of N_ijk counting is an atomic scatter-add
over a hash of the parent configuration.  TPUs have no fast scatter; the
TPU-native formulation is a *one-hot contraction on the MXU*:

    counts^T[r, q] = sum_t  onehot(child[t])[r] * onehot(cfg[t])[q]
                   = OH_child^T @ OH_cfg      # (r_pad, TILE_M)@(TILE_M, q_pad)

tiled over the instance axis so each one-hot slab lives in VMEM only
transiently, while the (r_pad, q_pad) accumulator stays resident in VMEM
across the sequential grid.  The table is kept transposed — child values on
sublanes, parent configurations on lanes — so an r <= 8 child pays 8
sublanes instead of 128 lanes of accumulator.

Layout rules of the TPU compiler, all met here:

* ``cfg``/``child`` arrive as (1, m_pad) rows in (1, TILE_M) blocks, TILE_M a
  multiple of 128 (a 1-D block must match XLA's 1-D tiling; a lane-major row
  does not care);
* both one-hots are built *transposed* from the rows by sublane-iota
  compares, so no in-kernel relayout is needed, and the contraction is an
  A @ B^T matmul over the TILE_M lanes;
* one-hot operands are bf16: 0/1 is exact in bf16, and the f32 accumulation
  keeps counts exact for m < 2^24.

Grid:      (m_pad // TILE_M,)  — sequential, accumulator revisited.
Padding:   out-of-range cfg values (>= q_pad, the m-padding sentinel) and
           child values (>= r_pad) produce all-zero one-hot columns and
           therefore count nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import VMEM_LIMIT_BYTES, accumulate_counts


def _kernel(cfg_ref, child_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    accumulate_counts(out_ref, cfg_ref[...], child_ref[...])


def contingency_counts_pallas(
    cfg: jax.Array,
    child: jax.Array,
    *,
    q_pad: int,
    r_pad: int,
    tile_m: int,
    interpret: bool,
) -> jax.Array:
    """(r_pad, q_pad) f32 transposed counts.  cfg/child: (1, m_pad) int32,
    m_pad % tile_m == 0 (ops.contingency_counts pads)."""
    m_pad = cfg.shape[1]
    if m_pad % tile_m != 0:
        raise ValueError(
            f"contingency_counts_pallas: m={m_pad} must be a multiple of "
            f"tile_m={tile_m} (ops.contingency_counts pads)")
    row = pl.BlockSpec((1, tile_m), lambda i: (0, i))
    return pl.pallas_call(
        _kernel,
        grid=(m_pad // tile_m,),
        in_specs=[row, row],
        out_specs=pl.BlockSpec((r_pad, q_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, q_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="bdeu_count",
    )(cfg, child)
