"""Shared plumbing of the BDeu Pallas kernels: backend resolution, padding,
the tile and VMEM constants the TPU compiler accepts, and the transposed
one-hot contraction both kernels accumulate counts with."""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128          # TPU vreg lane width: lane-axis block dims are multiples
SUBLANES = 8         # f32 sublane count: sublane-axis block dims are multiples
TILE_M = 512         # instance tile: a multiple of LANES (rows are lane-major)
# v5e has 128 MiB of VMEM per core; the compiler's default scoped limit
# (16 MiB) is too small for the insert sweep's output block at max_q = 4096.
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def resolve_interpret(interpret) -> bool:
    """``interpret=None`` resolves per backend at trace time: interpret mode
    on the CPU, the compiled kernel on a TPU.  Any other backend raises —
    these kernels are written for the TPU, and silently interpreting them
    elsewhere would hide which device ran them.  An explicit bool wins
    (compile rehearsals for a described TPU pass ``False`` on the CPU)."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"BDeu Pallas kernels run compiled on a TPU or interpreted on the "
        f"CPU; the default backend is {backend!r}")


def instance_tile(m: int, tile_m: int | None = None) -> int:
    """Instance-axis tile: ``tile_m`` or TILE_M, shrunk (to a lane multiple)
    for small m so a short dataset does not pay a full tile of padding."""
    return min(TILE_M if tile_m is None else tile_m, round_up(max(m, 1), LANES))


def pad_row(vals: jax.Array, length: int, fill: int) -> jax.Array:
    """(1, length) int32 lane-major row: ``vals`` then ``fill`` sentinels."""
    m = vals.shape[0]
    row = jnp.full((1, length), fill, dtype=jnp.int32)
    return row.at[0, :m].set(vals.astype(jnp.int32))


# A @ B^T: contract the lane (instance) axis of both transposed one-hots.
NT_DIMS = (((1,), (1,)), ((), ()))


def onehot_rows(vals: jax.Array, depth: int, dtype=jnp.bfloat16) -> jax.Array:
    """(depth, TILE_M) transposed one-hot of a (1, TILE_M) row of codes."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (depth, vals.shape[1]), 0)
    return (iota == vals).astype(dtype)


def accumulate_counts(acc_ref, cfg: jax.Array, child: jax.Array) -> None:
    """acc_ref (r_pad, q_pad) += counts of one (1, TILE_M) instance tile."""
    r_pad, q_pad = acc_ref.shape
    acc_ref[...] += jax.lax.dot_general(
        onehot_rows(child, r_pad), onehot_rows(cfg, q_pad), NT_DIMS,
        preferred_element_type=jnp.float32)
