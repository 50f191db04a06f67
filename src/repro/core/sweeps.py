"""The unified sweep engine — ONE API for every GES/ring/cGES delta rescoring.

Every rescoring step of the paper's algorithms is a *sweep*: score all
candidate single-edge changes toward one child (a column) or all children
(a matrix), as a batched delta against the current graph.  This module is the
single layer every driver goes through; the per-engine primitives live in
:mod:`repro.core.bdeu` and :mod:`repro.kernels.bdeu_sweep`.

Mapping of sweep kinds onto the paper (arXiv 2409.13314, Algorithm 1 / §2.2):

* ``kind="insert"`` — the **FES** candidate sweep: deltas for adding x -> y.
  This is the "evaluate all allowed arcs in parallel" step each ring process
  performs per round, and the whole of GES's forward stage.
* ``kind="delete"`` — the **BES** candidate sweep: deltas for removing
  x -> y.  Runs inside every ring process's constrained GES and in the final
  unrestricted fine-tuning pass.
* ``pids`` (candidate subset) — the paper's **restricted edge sets E_i**: a
  ring process with |E_i| ~ n/k allowed parents per column sweeps only those
  W candidates, which is the mechanism that makes the ring cheaper than
  monolithic GES.  ``pids=None`` sweeps all n candidates (the fine-tune /
  plain-GES case).
* ``pid_table`` (static (n, W) candidate table, one ``pids`` row per child —
  see :func:`repro.core.partition.pid_table_from_allowed`) — the whole-round
  restricted sweep: a masked **(W, n)** delta matrix whose entry [w, y] is
  the delta for toggling ``pid_table[y, w] -> y``.  This is what the
  compiled ``ges_jit``/shard_map-ring path initializes FES/BES from, so the
  fully-compiled ring pays W-wide matrix sweeps end-to-end instead of
  sweeping full-n and masking afterwards.  Rows are self-padded (pad slots
  hold ``y``), and padding comes back -inf like any other illegal toggle.

Backends (selected by ``counts_impl``):

* ``"segment" | "onehot" | "pallas"`` — the **loop** engine: one contingency
  table build per candidate (vmapped).
* ``"fused"`` — jnp segment-sum realizations of the fused sweeps: insert
  columns from ONE joint child-value-batched contraction
  (:func:`bdeu.fused_insert_scores`), delete columns from ONE family-table
  build marginalized over each parent slot
  (:func:`bdeu.fused_delete_scores`).
* ``"fused_pallas"`` — same math with the tiled Pallas kernels
  (``kernels/bdeu_sweep``): insert columns run the joint one-hot
  contraction kernel, and delete columns run the **VMEM-resident** delete
  kernel (``delete_marginals``) — the one current-family (max_q, r) table
  is accumulated in VMEM scratch and each parent slot's marginal is formed
  there, so only the (S + 1) slot tables reach HBM; the BDeu reduction is
  the jnp engine's own (interpret mode on CPU, compiled on TPU; identical
  masking/guard conventions to the jnp engines).

Convention (stronger than the raw bdeu primitives): returned columns and
matrices are **masked** — entries that are not a legal toggle (self-loops,
inserting an existing edge, deleting a missing edge, candidates outside a
``pids`` subset's real extent via self-padding) are -inf under EVERY backend,
so callers cannot select them by forgetting a mask and all backends agree
entry-for-entry.  Graph-level validity (acyclicity, max_parents, allowed-edge
sets E_i, q-guard for inserts) remains the caller's mask, as before.

Two ORTHOGONAL mesh axes
------------------------

Sweeps can be distributed along two independent mesh axes that compose into
a 2-D (or, with the ring, 3-D) device mesh:

* **scoring-TP** (``axis_name``/``axis_size`` on the matrix bodies): the
  CHILD axis is split — each device scores n/axis_size children's columns
  and an ``all_gather`` reassembles the delta matrix.  Work partitioning;
  every device still reads the full (m, n) data shard it holds.
* **data axis** (``data_axis_name``, new): the INSTANCE axis is split —
  each device holds only an m/d row-shard of ``data`` and contracts it into
  partial contingency tables; ONE ``psum`` per table (placed inside the bdeu
  primitives / kernel ops wrappers, before the m-independent BDeu reduction)
  rebuilds the global counts.  Ragged m is padded with sentinel rows of
  value ``r_max`` (out of range for every variable — counting-neutral in
  all backends), so sharded sweeps are table-identical to single-device.
  The VMEM Pallas delete kernel marginalizes the *local* family table, so
  under data sharding ``"fused_pallas"`` deletes route to the two-step
  psum-able path.

The host-facing switch is ``sweep(..., data_shards=d)``, which pads, builds
a cached jitted ``shard_map`` over a d-device ``("data",)`` mesh and runs
the same bodies inside it.  The compiled ring threads ``data_axis_name``
explicitly through ``ges_jit_body`` on a 2-D (ring x data) mesh.

Family-score cache
------------------

:func:`sweep_column_cached` guards a column sweep with the persistent
device-resident cache of :mod:`repro.core.score_cache`.  Key = exact packed
``(kind, child, parent-bitmask-of-child, scope)`` — the column is a pure
function of those (plus the static sweep program), ``scope`` naming the
candidate restriction (ring members hash their allowed column into it; 0
for full-n).  Keys match word-for-word (the hash only places entries in a
set-associative table), so cached trajectories are bitwise-identical to
uncached.  Eviction is prioritized: recency step + a bounded bonus for
columns still holding a positive delta (PER-flavoured).  See the
score_cache module docstring for the full contract.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import bdeu, score_cache

Array = jax.Array
NEG_INF = -jnp.inf

KINDS = ("insert", "delete")

# Mesh-axis name used by the host-facing ``sweep(..., data_shards=d)`` path.
DATA_AXIS = "data"

KIND_CODES = {"insert": score_cache.KIND_INSERT,
              "delete": score_cache.KIND_DELETE}


def pad_data_rows(data: Array, r_max: int, d: int) -> Array:
    """Pad the instance axis to a multiple of ``d`` with sentinel rows.

    Sentinel value ``r_max`` is out of range for EVERY variable (values are
    0..arity-1 <= r_max - 1), which all count backends treat as
    counting-neutral (zero one-hot rows / explicit overflow segments /
    kernel sentinel contract) — so ragged m % d != 0 sharding is exact.
    """
    m = int(data.shape[0])
    m_pad = ((m + d - 1) // d) * d
    if m_pad == m:
        return data
    pad = jnp.full((m_pad - m, data.shape[1]), r_max, dtype=data.dtype)
    return jnp.concatenate([data, pad], axis=0)


def _check_kind(kind: str) -> bool:
    if kind not in KINDS:
        raise ValueError(f"kind must be one of {KINDS}, got {kind!r}")
    return kind == "insert"


def _check_pids(pids, n: int, name: str = "pids") -> Array:
    """Validate a candidate-id vector/table against the variable count n.

    Shape problems (wrong rank, more candidates than variables) and
    out-of-range ids raise ``ValueError`` immediately instead of flowing into
    the gather as silent wrong shapes / clamped indices.  Value checks are
    skipped for traced arrays (inside jit the caller's ids are assumed
    pre-validated — the public ``sweep`` entry point sees concrete arrays).
    """
    pids = jnp.asarray(pids)
    if not jnp.issubdtype(pids.dtype, jnp.integer):
        raise ValueError(f"{name} must be integer-typed, got {pids.dtype}")
    width = pids.shape[-1]
    if width > n:
        raise ValueError(
            f"{name} has {width} candidates per column but only n = {n} "
            f"variables exist — pad with the child id (self-loop), not by "
            f"exceeding n")
    if not isinstance(pids, jax.core.Tracer) and pids.size:
        vals = np.asarray(pids)
        if vals.min() < 0 or vals.max() >= n:
            bad = vals[(vals < 0) | (vals >= n)]
            raise ValueError(
                f"{name} contains out-of-range variable ids {bad[:8]} "
                f"(valid range [0, {n}))")
    return pids


# ---------------------------------------------------------------------------
# Column sweeps (incremental rescoring: only column y changed)
# ---------------------------------------------------------------------------

def sweep_column_body(data, arities, adj, y, pids, ess, max_q, r_max,
                      counts_impl, kind, data_axis_name=None):
    """Traceable masked delta column — callable from inside jit/shard_map.

    Returns (n,) deltas for toggling x -> y over all candidates x, or (W,)
    over the ``pids`` subset.  See the module docstring for the masking
    convention; with a fused ``counts_impl`` the whole column costs one joint
    contraction (insert) or one family-table build (delete) instead of one
    table build per candidate.  With ``data_axis_name`` every count build
    contracts the local m/d shard and psums (module docstring: data axis).
    """
    insert = _check_kind(kind)
    n = adj.shape[0]
    pm = adj.astype(bool)[:, y]
    base = bdeu.local_score_masked(
        data, arities, y, pm, ess, max_q, r_max, counts_impl,
        data_axis_name=data_axis_name)
    cand = jnp.arange(n, dtype=jnp.int32) if pids is None else pids

    if counts_impl in bdeu.FUSED_IMPLS:
        fn = bdeu.fused_insert_scores if insert else bdeu.fused_delete_scores
        deltas = fn(data, arities, y, pm, ess, max_q, r_max, counts_impl,
                    pids=pids, data_axis_name=data_axis_name) - base
    elif insert:
        # The ONE loop-engine insert primitive (incremental config
        # encoding) — shared with bdeu._deltas_impl's full matrix, so a
        # restricted column is bitwise equal to the matching full-n
        # matrix entries and full-n tie-breaks transfer exactly.
        deltas = bdeu.loop_insert_scores(
            data, arities, y, pm, ess, max_q, r_max, counts_impl,
            pids=pids, data_axis_name=data_axis_name) - base
    else:
        def per_parent(x):
            return bdeu.local_score_masked(
                data, arities, y, pm.at[x].set(False), ess, max_q, r_max,
                counts_impl, data_axis_name=data_axis_name)

        deltas = jax.vmap(per_parent)(cand) - base

    in_pa = jnp.take(pm, cand)
    legal = (cand != y) & (~in_pa if insert else in_pa)
    return jnp.where(legal, deltas, NEG_INF)


@partial(jax.jit, static_argnames=("ess", "max_q", "r_max", "counts_impl",
                                   "kind"))
def _sweep_column(data, arities, adj, y, pids, ess, max_q, r_max,
                  counts_impl, kind):
    return sweep_column_body(data, arities, adj, y, pids, ess, max_q, r_max,
                             counts_impl, kind)


# ---------------------------------------------------------------------------
# Matrix sweeps (full (n, n) delta matrices: FES/BES initialization)
# ---------------------------------------------------------------------------

def sweep_matrix_body(data, arities, adj, ess, max_q, r_max, counts_impl,
                      kind, child_chunk=None, axis_name=None,
                      axis_size: int = 1, data_axis_name=None):
    """Traceable masked (n, n) delta matrix D[x, y] for toggling x -> y.

    ``axis_name``/``axis_size``: optional mesh axis over which the child
    sweep is split (scoring-TP inside a ring process; see bdeu._deltas_impl).
    ``data_axis_name``: optional ORTHOGONAL mesh axis sharding the instance
    axis (module docstring) — composes freely with the child split.
    """
    insert = _check_kind(kind)
    fn = bdeu.insert_deltas if insert else bdeu.delete_deltas
    D = fn(data, arities, adj, ess, max_q, r_max, counts_impl, child_chunk,
           axis_name=axis_name, axis_size=axis_size,
           data_axis_name=data_axis_name)
    n = adj.shape[0]
    eye = jnp.eye(n, dtype=bool)
    has_edge = adj.astype(bool)
    legal = (~has_edge if insert else has_edge) & ~eye
    return jnp.where(legal, D, NEG_INF)


@partial(jax.jit, static_argnames=("ess", "max_q", "r_max", "counts_impl",
                                   "kind", "child_chunk"))
def _sweep_matrix(data, arities, adj, ess, max_q, r_max, counts_impl, kind,
                  child_chunk):
    return sweep_matrix_body(data, arities, adj, ess, max_q, r_max,
                             counts_impl, kind, child_chunk)


# ---------------------------------------------------------------------------
# Restricted matrix sweeps (the compiled ring's W-wide per-round rescoring)
# ---------------------------------------------------------------------------

def sweep_matrix_restricted_body(data, arities, adj, pid_table, ess, max_q,
                                 r_max, counts_impl, kind, child_chunk=None,
                                 axis_name=None, axis_size: int = 1,
                                 data_axis_name=None):
    """Traceable masked (W, n) delta matrix over a static candidate table.

    ``pid_table``: (n, W) int32, row y = the candidate parents of child y
    (the ring's E_i column, self-padded to the static width W).  Entry
    [w, y] is the masked delta for toggling ``pid_table[y, w] -> y`` — the
    same engine-masked values a full (n, n) sweep would put at
    ``[pid_table[y, w], y]``, with padding slots (and any other illegal
    toggle) at -inf.  Every backend pays W-wide column cost: the loop engine
    builds W tables per child, the fused engines gather the W candidate data
    columns *before* the joint contraction (insert) / build the W
    marginalization maps only (delete).

    ``axis_name``/``axis_size``: optional mesh axis over which the child
    sweep is split (scoring-TP inside a ring process, mirroring
    :func:`sweep_matrix_body`): each device scores n/axis_size children's
    W-wide columns, then an all-gather reassembles the (W, n) matrix.
    """
    _check_kind(kind)
    n = adj.shape[0]

    def per_child(args):
        y, pids = args
        return sweep_column_body(data, arities, adj, y, pids, ess, max_q,
                                 r_max, counts_impl, kind,
                                 data_axis_name=data_axis_name)

    if counts_impl in bdeu.FUSED_IMPLS and child_chunk is None:
        # Same memory bound as bdeu._deltas_impl: a fused child column
        # materializes an (r_max, r_max, max_q, W) count slab — map children
        # sequentially so one slab lives at a time.
        child_chunk = 1

    def map_children(ids, rows):
        cnt = ids.shape[0]
        if child_chunk is None or child_chunk >= cnt:
            return jax.vmap(per_child)((ids, rows))              # (cnt, W)
        return jax.lax.map(per_child, (ids, rows),
                           batch_size=min(child_chunk, cnt))

    if axis_name is not None:
        per = -(-n // axis_size)                    # children per device
        i = jax.lax.axis_index(axis_name)
        ids = jnp.clip(i * per + jnp.arange(per), 0, n - 1).astype(jnp.int32)
        cols_l = map_children(ids, jnp.take(pid_table, ids, axis=0))
        cols = jax.lax.all_gather(cols_l, axis_name, axis=0,
                                  tiled=True)[:n]                # (n, W)
        return cols.T
    children = jnp.arange(n, dtype=jnp.int32)
    return map_children(children, pid_table).T                   # (W, n)


@partial(jax.jit, static_argnames=("ess", "max_q", "r_max", "counts_impl",
                                   "kind", "child_chunk"))
def _sweep_matrix_restricted(data, arities, adj, pid_table, ess, max_q, r_max,
                             counts_impl, kind, child_chunk):
    return sweep_matrix_restricted_body(data, arities, adj, pid_table, ess,
                                        max_q, r_max, counts_impl, kind,
                                        child_chunk)


# ---------------------------------------------------------------------------
# Cache-guarded column sweeps (persistent family-score cache)
# ---------------------------------------------------------------------------

def sweep_column_cached(cache, data, arities, adj, y, pids, ess, max_q,
                        r_max, counts_impl, kind, scope=0,
                        data_axis_name=None):
    """Column sweep guarded by the persistent family-score cache.

    Returns ``(col, cache')``.  On a hit the whole column compute (the O(m)
    count contraction) is skipped via ``lax.cond``; on a miss the computed
    column is stored with prioritized eviction.  Key = exact packed
    (kind, y, parents-of-y, scope) — see :mod:`repro.core.score_cache` for
    why cached trajectories are bitwise-identical to uncached.  Traceable:
    lives inside ``lax.while_loop``/``lax.scan`` (the compiled FES/BES
    loops thread ``cache`` through their carries).
    """
    _check_kind(kind)
    pm = adj.astype(bool)[:, y]

    def compute():
        return sweep_column_body(data, arities, adj, y, pids, ess, max_q,
                                 r_max, counts_impl, kind,
                                 data_axis_name=data_axis_name)

    return score_cache.lookup_or_compute(
        cache, KIND_CODES[kind], y, pm, scope, compute)


# ---------------------------------------------------------------------------
# Host-facing data-axis sharding: sweep(..., data_shards=d)
# ---------------------------------------------------------------------------

def _data_mesh(d: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < d:
        raise ValueError(
            f"data_shards={d} needs {d} devices but only {len(devs)} are "
            f"visible — on CPU set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={d} before importing jax")
    return Mesh(np.array(devs[:d]), (DATA_AXIS,))


@lru_cache(maxsize=None)
def _sharded_sweep_fn(d: int, mode: str, has_pids: bool, ess, max_q: int,
                      r_max: int, counts_impl: str, kind: str,
                      child_chunk):
    """Cached jitted shard_map program for one static sweep configuration.

    ``mode``: "column" | "matrix" | "matrix_restricted".  Data is sharded
    along the mesh's data axis; everything else is replicated, and the
    psum'd result is replicated (identical on every device) by construction.
    """
    mesh = _data_mesh(d)

    if mode == "column":
        if has_pids:
            def body(data, arities, adj, y, pids):
                return sweep_column_body(
                    data, arities, adj, y, pids, ess, max_q, r_max,
                    counts_impl, kind, data_axis_name=DATA_AXIS)
            in_specs = (P(DATA_AXIS), P(), P(), P(), P())
        else:
            def body(data, arities, adj, y):
                return sweep_column_body(
                    data, arities, adj, y, None, ess, max_q, r_max,
                    counts_impl, kind, data_axis_name=DATA_AXIS)
            in_specs = (P(DATA_AXIS), P(), P(), P())
    elif mode == "matrix":
        def body(data, arities, adj):
            return sweep_matrix_body(
                data, arities, adj, ess, max_q, r_max, counts_impl, kind,
                child_chunk, data_axis_name=DATA_AXIS)
        in_specs = (P(DATA_AXIS), P(), P())
    else:
        def body(data, arities, adj, pid_table):
            return sweep_matrix_restricted_body(
                data, arities, adj, pid_table, ess, max_q, r_max,
                counts_impl, kind, child_chunk, data_axis_name=DATA_AXIS)
        in_specs = (P(DATA_AXIS), P(), P(), P())

    # check_vma=False: psum'd counts are replicated in a way the varying-
    # manual-axes checker cannot see
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False))


# ---------------------------------------------------------------------------
# The single public entry point
# ---------------------------------------------------------------------------

def sweep(
    data: Array,
    arities: Array,
    adj: Array,
    *,
    kind: str,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "segment",
    y: Optional[int] = None,
    pids: Optional[Array] = None,
    pid_table: Optional[Array] = None,
    child_chunk: Optional[int] = None,
    data_shards: int = 1,
) -> Array:
    """Masked BDeu delta sweep — the one API behind GES, the ring, and cGES.

    * ``kind="insert"`` / ``"delete"`` — FES / BES candidate rescoring.
    * ``y=None`` — full (n, n) delta matrix over all children;
      ``y=<child>`` — the (n,) column for one child.
    * ``pids=None`` — all n candidates; ``pids=<(W,) int32>`` — the
      restricted subset (ring E_i), returning a (W,) column whose cost
      scales with W under every backend.
    * ``pid_table=<(n, W) int32>`` (matrix sweeps only) — per-child
      restricted candidates, returning the masked (W, n) delta matrix whose
      entry [w, y] toggles ``pid_table[y, w] -> y``; the compiled ring's
      W-wide per-round rescoring.

    Candidate ids are validated up front: a ``pids``/``pid_table`` whose
    width exceeds n or that contains ids outside [0, n) raises ValueError
    instead of silently gathering wrong shapes.

    ``data_shards=d`` (> 1) shards the INSTANCE axis over a d-device
    ``("data",)`` mesh: ragged m is padded with counting-neutral sentinel
    rows, each device contracts its m/d shard and one psum per table
    rebuilds the global counts — results are table-identical to
    ``data_shards=1`` under every backend (module docstring: data axis).

    Dispatches to the loop / fused-jnp / fused-Pallas backend named by
    ``counts_impl``; all backends return identical masked columns (see the
    module docstring for the -inf convention at illegal toggles).
    """
    _check_kind(kind)
    bdeu.check_counts_impl(counts_impl)
    n = adj.shape[0]
    d = 1 if data_shards is None else int(data_shards)
    if d < 1:
        raise ValueError(f"data_shards must be >= 1, got {data_shards}")
    if d > 1:
        data = pad_data_rows(jnp.asarray(data), r_max, d)
    if pid_table is not None:
        if y is not None or pids is not None:
            raise ValueError("pid_table is a whole-matrix restriction — "
                             "pass either pid_table or (y, pids), not both")
        pid_table = _check_pids(pid_table, n, name="pid_table")
        if pid_table.ndim != 2 or pid_table.shape[0] != n:
            raise ValueError(f"pid_table must be (n, W) = ({n}, W), got "
                             f"{pid_table.shape}")
        if d > 1:
            fn = _sharded_sweep_fn(d, "matrix_restricted", True, ess, max_q,
                                   r_max, counts_impl, kind, child_chunk)
            return fn(data, arities, adj, pid_table)
        return _sweep_matrix_restricted(data, arities, adj, pid_table, ess,
                                        max_q, r_max, counts_impl, kind,
                                        child_chunk)
    if y is None:
        if pids is not None:
            raise ValueError("pids restriction requires a column sweep "
                             "(pass y) — for a restricted matrix pass "
                             "pid_table")
        if d > 1:
            fn = _sharded_sweep_fn(d, "matrix", False, ess, max_q, r_max,
                                   counts_impl, kind, child_chunk)
            return fn(data, arities, adj)
        return _sweep_matrix(data, arities, adj, ess, max_q, r_max,
                             counts_impl, kind, child_chunk)
    if pids is not None:
        pids = _check_pids(pids, n, name="pids")
        if pids.ndim != 1:
            raise ValueError(f"pids must be 1-D (W,), got {pids.shape}")
    if d > 1:
        fn = _sharded_sweep_fn(d, "column", pids is not None, ess, max_q,
                               r_max, counts_impl, kind, child_chunk)
        args = (data, arities, adj, jnp.int32(y))
        return fn(*args, pids) if pids is not None else fn(*args)
    return _sweep_column(data, arities, adj, jnp.int32(y), pids, ess, max_q,
                         r_max, counts_impl, kind)
