"""Pallas TPU kernels: fused all-candidate sweeps for BDeu deltas.

Two kernels, one per GES phase.  Both follow the layout rules of
``kernels/bdeu_count``: instance codes arrive as (1, m_pad) lane-major rows,
one-hots are built transposed from sublane-iota compares (no in-kernel
relayout), 0/1 one-hot operands are bf16 with f32 accumulation (exact), and
every block's last two dims are (8, 128)-aligned or span the whole array.

**Insert (FES)** — ``sweep_counts_pallas``.  The candidate sweep for one
child evaluates all n families (Pa + {x}) at once.  The extended parent
configuration factorizes, ``cfg_x = (cfg0, X_x)``, so the whole sweep is ONE
joint contraction batched over the child's value b and the candidate's
value a:

    counts[b, a, j0, x] = sum_t [child[t]=b][cfg0[t]=j0][data[t,x]=a]
                        = OH(cfg0 | child=b)^T @ [data == a]

i.e. r_max^2 (q_pad, m) @ (m, n_pad) matmuls with full lane utilization.
The cfg one-hot of child value b is built once per grid step and reused for
all r_max candidate values: the a-loop runs in-kernel over (TILE_M, 128)
``data == a`` masks, so no (TILE_M, TILE_N, r_max) one-hot is ever reshaped
into lanes.

Grid:      (r_max, n_pad // 128, m_pad // TILE_M) — m innermost and
           sequential, so the (r_max, q_pad, 128) output block stays resident
           in VMEM across the m sweep and is revisited.
Padding:   out-of-range cfg (q_pad) or child (r_max) rows produce all-zero
           one-hot rows and count nothing; padded data columns hold the
           sentinel r_max and yield all-zero count columns.  Zero-count cells
           cancel exactly in the BDeu sum (lgamma(N + a) - lgamma(a) = 0 at
           N = 0), so padding is exact.

**Delete (BES)** — ``delete_marginals_pallas``.  Every candidate table
``counts(Pa - {x})`` is a *marginalization* of the ONE current-family table
over parent slot x (see ``bdeu.fused_delete_scores`` for the radix-code
algebra).  The kernel accumulates that table in a VMEM scratch across the m
grid and, on the final grid step, forms each parent slot's marginal in VMEM
and writes the stack of (S + 1) tables — the family table first — so the
data is read once for the whole delete column.  The BDeu reduction (lgamma)
stays outside the kernel, in the same jnp function the ``fused`` engine
uses, so both engines score identical tables with identical code.

Grid:      (m_pad // TILE_M,) — sequential; the (r_pad, q_pad) scratch is
           revisited.
Marginalization:  TPU has no fast scatter, so the digit-sum
           M[j'] = sum_{t(j0) = j'} counts[j0] is a scatter-by-matmul against
           the one-hot of t(j0) (precomputed per slot by the caller), chunked
           over j0.  Counts exceed bf16's exact-integer range, so that dot
           runs at HIGHEST (f32) precision.  Identity slots (t = j0) copy the
           family table, so padded slots are exact no-ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (LANES, NT_DIMS, VMEM_LIMIT_BYTES, accumulate_counts,
                      onehot_rows)


def _insert_kernel(cfg_ref, child_ref, data_ref, out_ref, *, r_max: int):
    b = pl.program_id(0)

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    q_pad = out_ref.shape[2]
    # instances with child value b keep their cfg; others hit the sentinel
    sel = jnp.where(child_ref[...] == b, cfg_ref[...], q_pad)    # (1, TILE_M)
    oh_cfg = onehot_rows(sel, q_pad)                           # (q_pad, TILE_M)
    data = data_ref[...]                                       # (TILE_M, 128)
    for a in range(r_max):
        out_ref[0, a] += jnp.dot(oh_cfg, (data == a).astype(jnp.bfloat16),
                                 preferred_element_type=jnp.float32)


def sweep_counts_pallas(
    cfg: jax.Array,
    child: jax.Array,
    data: jax.Array,
    *,
    q_pad: int,
    r_max: int,
    tile_m: int,
    interpret: bool,
) -> jax.Array:
    """(r_max, r_max, q_pad, n_pad) f32 joint sweep counts [b, a, j0, x].

    cfg/child: (1, m_pad) int32 rows; data: (m_pad, n_pad) int32 with
    m_pad % tile_m == 0 and n_pad % 128 == 0 (ops.sweep_counts pads).
    """
    m_pad, n_pad = data.shape
    if m_pad % tile_m != 0 or n_pad % LANES != 0:
        raise ValueError(
            f"sweep_counts_pallas: data {data.shape} must be a multiple of "
            f"({tile_m}, {LANES}) (ops.sweep_counts pads)")
    row = pl.BlockSpec((1, tile_m), lambda b, c, i: (0, i))
    return pl.pallas_call(
        functools.partial(_insert_kernel, r_max=r_max),
        grid=(r_max, n_pad // LANES, m_pad // tile_m),
        in_specs=[row, row,
                  pl.BlockSpec((tile_m, LANES), lambda b, c, i: (i, c))],
        out_specs=pl.BlockSpec((1, r_max, q_pad, LANES),
                               lambda b, c, i: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((r_max, r_max, q_pad, n_pad),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="bdeu_sweep_insert",
    )(cfg, child, data)


def _delete_kernel(cfg_ref, child_ref, tmap_ref, out_ref, acc_ref, *,
                   chunk_q: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- phase 1: accumulate the current-family table into VMEM scratch ----
    accumulate_counts(acc_ref, cfg_ref[...], child_ref[...])

    # ---- phase 2 (final step): every slot's marginal, formed in VMEM -------
    @pl.when(step == pl.num_programs(0) - 1)
    def _marginalize():
        r_pad, q_pad = acc_ref.shape
        out_ref[0] = acc_ref[...]

        def slot(s, carry):
            def chunk(c, marg):
                # rows j0 in [start, start + chunk_q) scatter to t(j0)
                start = pl.multiple_of(c * chunk_q, chunk_q)
                t = tmap_ref[pl.ds(s, 1), pl.ds(start, chunk_q)]  # (1, chunk)
                sel = onehot_rows(t, q_pad, jnp.float32)       # (q_pad, chunk)
                rows = acc_ref[:, pl.ds(start, chunk_q)]       # (r_pad, chunk)
                return marg + jax.lax.dot_general(
                    rows, sel, NT_DIMS,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)

            out_ref[s + 1] = jax.lax.fori_loop(
                0, q_pad // chunk_q, chunk,
                jnp.zeros((r_pad, q_pad), jnp.float32))
            return carry

        jax.lax.fori_loop(0, tmap_ref.shape[0], slot, 0)


def delete_marginals_pallas(
    cfg: jax.Array,
    child: jax.Array,
    tmap: jax.Array,
    *,
    r_pad: int,
    tile_m: int,
    interpret: bool,
) -> jax.Array:
    """(S + 1, r_pad, q_pad) f32 transposed tables: [0] the family table,
    [s + 1] its marginal over parent slot s.

    cfg/child: (1, m_pad) int32 rows, m_pad % tile_m == 0.  tmap: (S, q_pad)
    int32, row s the digit-sum relabeling t(j0) of slot s (q_pad % 128 == 0).
    """
    m_pad = cfg.shape[1]
    n_slots, q_pad = tmap.shape
    if m_pad % tile_m != 0 or q_pad % LANES != 0:
        raise ValueError(
            f"delete_marginals_pallas: m={m_pad} must be a multiple of "
            f"tile_m={tile_m} and q_pad={q_pad} of {LANES} "
            f"(ops.delete_marginals pads)")
    chunk_q = 256 if q_pad % 256 == 0 else LANES
    row = pl.BlockSpec((1, tile_m), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_delete_kernel, chunk_q=chunk_q),
        grid=(m_pad // tile_m,),
        in_specs=[row, row,
                  pl.BlockSpec((n_slots, q_pad), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((n_slots + 1, r_pad, q_pad),
                               lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_slots + 1, r_pad, q_pad),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((r_pad, q_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="bdeu_sweep_delete",
    )(cfg, child, tmap)
