"""BDeu scoring — the compute hot-spot of GES/cGES.

Two mirrored engines with identical semantics:

* **host** (numpy, sparse-exact): contingency tables via ``np.unique`` over the
  *observed* parent configurations only.  Valid for arbitrary arities / parent
  set sizes; this is the oracle used in tests and the default for paper-scale
  host orchestration.

* **device** (jnp, dense-padded, jit-safe): parent sets are padded to a static
  ``max_parents`` with phantom arity-1 slots, contingency tables are dense
  ``(max_q, r_max)`` arrays built either by ``segment_sum`` or by a one-hot
  matmul (the MXU-friendly TPU path; see ``repro.kernels.bdeu_count``).
  Configurations with zero counts contribute exactly 0 to the BDeu sum
  (lgamma(a) - lgamma(0 + a) == 0), so dense padding is *exact*, not an
  approximation.

The device engine's candidate sweeps additionally have a **fused** mode
(``counts_impl="fused"`` / ``"fused_pallas"``):

* insert (FES): all n candidate contingency tables of a child are produced by
  ONE joint (child-value-batched) one-hot contraction instead of n independent
  builds — see the "Fused all-candidate sweep engine" section below and
  ``repro.kernels.bdeu_sweep`` for the tiled Pallas realization.  With a
  candidate subset ``pids`` (the ring's restricted E_i), the candidate data
  columns are gathered *before* the contraction so the fused cost scales with
  W = |pids|, not n.
* delete (BES): every candidate table ``counts(Pa - {x})`` is a
  *marginalization* of the ONE current-family (q0, r) table over parent slot
  x — :func:`fused_delete_scores` builds that table once and reads the whole
  delete column off it with zero re-counting (n table builds -> 1).  Under
  ``"fused_pallas"`` the build and the per-slot marginalizations happen
  inside ONE VMEM-resident Pallas kernel
  (``kernels/bdeu_sweep.delete_marginals``), which writes one table per
  parent slot; the BDeu reduction is the same jnp code as ``"fused"``.

The unified caller-facing layer over these primitives is ``repro.core.sweeps``.

The BDeu local score of child i with parent set Pa (Heckerman et al. 1995):

    sum_j [ lgamma(ess/q) - lgamma(N_ij + ess/q) ]
  + sum_jk [ lgamma(N_ijk + ess/(q r)) - lgamma(ess/(q r)) ]

with q = prod of parent arities, r = arity of the child.  A uniform structure
prior is used (log P(G) = 0), as is standard.
"""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.scipy.special import gammaln

Array = jax.Array

_lgamma_np = np.frompyfunc(math.lgamma, 1, 1)


def lgamma_np(x: np.ndarray) -> np.ndarray:
    """Exact (libm) log-gamma on host arrays."""
    return _lgamma_np(np.asarray(x, dtype=np.float64)).astype(np.float64)


# ---------------------------------------------------------------------------
# Host engine — sparse exact
# ---------------------------------------------------------------------------

def local_score_np(
    data: np.ndarray,
    arities: np.ndarray,
    child: int,
    parents: Sequence[int],
    ess: float = 10.0,
) -> float:
    """Exact BDeu local score of ``child`` given ``parents`` on host.

    data: (m, n) int array of category indices; arities: (n,).
    Only observed parent configurations are materialized (zero-count
    configurations contribute 0 by cancellation).
    """
    parents = list(parents)
    r = int(arities[child])
    q = 1
    for p in parents:
        q *= int(arities[p])
    if parents:
        # radix-encode observed parent configurations
        cfg = np.zeros(data.shape[0], dtype=np.int64)
        for p in parents:
            cfg = cfg * int(arities[p]) + data[:, p]
        uniq, inv = np.unique(cfg, return_inverse=True)
        flat = inv * r + data[:, child]
        counts = np.bincount(flat, minlength=uniq.size * r).reshape(uniq.size, r)
    else:
        counts = np.bincount(data[:, child], minlength=r).reshape(1, r)
    n_ij = counts.sum(axis=1)
    a_j = ess / q
    a_jk = ess / (q * r)
    term_j = lgamma_np(np.full_like(n_ij, a_j, dtype=np.float64)) - lgamma_np(n_ij + a_j)
    term_jk = lgamma_np(counts + a_jk) - lgamma_np(np.full_like(counts, a_jk, dtype=np.float64))
    return float(term_j.sum() + term_jk.sum())


def graph_score_np(
    data: np.ndarray, arities: np.ndarray, adj: np.ndarray, ess: float = 10.0
) -> float:
    """Total BDeu of a DAG = sum of local scores (decomposability)."""
    total = 0.0
    for y in range(adj.shape[0]):
        total += local_score_np(data, arities, y, list(np.flatnonzero(adj[:, y])), ess)
    return total


def pairwise_similarity_np(
    data: np.ndarray, arities: np.ndarray, ess: float = 10.0
) -> np.ndarray:
    """Paper Eq. (4):  s(X_i, X_j) = BDeu(X_i <- X_j) - BDeu(X_i, no parent).

    Returned matrix is symmetrized (the measure is symmetric up to finite-sample
    noise; the paper treats it as symmetric).
    """
    n = data.shape[1]
    s = np.zeros((n, n), dtype=np.float64)
    base = np.array([local_score_np(data, arities, i, [], ess) for i in range(n)])
    for i in range(n):
        for j in range(i + 1, n):
            sij = local_score_np(data, arities, i, [j], ess) - base[i]
            sji = local_score_np(data, arities, j, [i], ess) - base[j]
            s[i, j] = s[j, i] = 0.5 * (sij + sji)
    return s


# ---------------------------------------------------------------------------
# Device engine — dense padded, jit-safe
# ---------------------------------------------------------------------------

def _slot_encode(data: Array, arities: Array, parent_mask: Array):
    """Radix-encode parent configurations for a *masked* parent set.

    parent_mask: (n,) bool — which variables are parents.  Masked-out variables
    become phantom arity-1 slots (value 0), so the true q is the product of the
    selected arities and the config index stays < q.

    Returns (cfg, q): cfg (m,) int32 config index, q scalar int32 (true q).
    """
    # int32 radix encoding: valid whenever the true q fits the dense table
    # bound (max_q << 2^31); overflowing candidates are masked to -inf by the
    # log-domain guard in local_score_masked, and their (wrapped) cfg values
    # are clipped before counting, so they never corrupt memory or counts.
    #
    # Fully vectorized (no sequential scan over the n slots): the Horner
    # recurrence cfg = ((0*ar_0 + v_0)*ar_1 + v_1)... expands to
    # sum_i v_i * prod_{j>i} ar_j, and int32 arithmetic is exact modular
    # arithmetic, so the place-value sum is BITWISE identical to the scan it
    # replaces — including on wrapping (guarded) parent sets.  This keeps the
    # per-column cost of a *restricted* W-wide sweep from being dominated by
    # an O(n)-step sequential encode.
    slot_ar = jnp.where(parent_mask, arities, 1).astype(jnp.int32)
    slot_val = jnp.where(parent_mask[None, :], data, 0).astype(jnp.int32)
    rev = jnp.cumprod(slot_ar[::-1])                 # prod of trailing slots
    q = rev[-1]
    low = jnp.concatenate([rev[::-1][1:], jnp.ones(1, jnp.int32)])
    cfg = (slot_val * low[None, :]).sum(axis=1, dtype=jnp.int32)
    return cfg, q


def _bdeu_from_counts(counts: Array, q, r, ess: float) -> Array:
    """BDeu sum given dense ``(..., Q, R)`` count tables and true q, r.

    Rows >= q and columns >= r are guaranteed zero-count; zero-count cells
    cancel exactly, but the *per-row* ``lgamma(ess/q) - lgamma(N_ij + ess/q)``
    term is also exactly 0 for empty rows, so no masking is needed beyond using
    the true q, r in the hyperparameters.

    Vectorized over leading batch dims: ``q`` may carry the same batch shape
    as ``counts[..., 0, 0]`` (the fused sweep passes the per-candidate
    ``q0 * r_x`` vector and reduces a whole ``(n, Q, R)`` slab to the ``(n,)``
    score column in one shot); scalar ``q``/``r`` recovers the single-family
    behaviour.
    """
    q = jnp.asarray(q).astype(jnp.float32)
    r = jnp.asarray(r).astype(jnp.float32)
    a_j = (ess / q)[..., None]
    a_jk = (ess / (q * r))[..., None, None]
    n_ij = counts.sum(axis=-1)
    term_j = gammaln(a_j) - gammaln(n_ij + a_j)
    term_jk = gammaln(counts + a_jk) - gammaln(a_jk)
    return term_j.sum(-1) + term_jk.sum((-2, -1))


def _psum_counts(counts: Array, data_axis_name: str | None) -> Array:
    """Contingency tables are additive over instances: when the m axis is
    sharded over ``data_axis_name`` each device builds its partial table and
    ONE psum reconstructs the global counts — placed here, before the
    (m-independent) BDeu reduction, so the reduction itself never needs to
    know about the mesh."""
    if data_axis_name is None:
        return counts
    return jax.lax.psum(counts, data_axis_name)


def _dense_counts_segment(cfg: Array, child_col: Array, r_max: int, max_q: int) -> Array:
    """(max_q, r_max) contingency table via segment-sum (CPU/debug path).

    Out-of-range child values (the data-axis sharder pads ragged m with
    sentinel rows of value r_max, out of range for every variable) are routed
    to an explicit overflow segment and sliced off — same OOB-drop idiom as
    ``kernels/bdeu_sweep/ref.py``; bitwise-identical for in-range rows.
    """
    ok = (child_col >= 0) & (child_col < r_max)
    flat = jnp.where(ok, jnp.clip(cfg, 0, max_q - 1) * r_max + child_col,
                     max_q * r_max)
    counts = jax.ops.segment_sum(
        jnp.ones_like(flat, dtype=jnp.float32), flat,
        num_segments=max_q * r_max + 1
    )[: max_q * r_max]
    return counts.reshape(max_q, r_max)


def _dense_counts_onehot(cfg: Array, child_col: Array, r_max: int, max_q: int) -> Array:
    """(max_q, r_max) contingency table as one-hot matmul — MXU-friendly.

    counts = OH(cfg)^T @ OH(child):  (max_q, m) @ (m, r_max).  Exact for
    m <= 2^24 in f32.  This is the TPU-native replacement for GPU scatter-add;
    the Pallas kernel in repro/kernels/bdeu_count tiles the same contraction.
    (Sentinel rows with child = r_max one-hot to the zero row — counting-
    neutral without any explicit guard.)
    """
    cfg = jnp.clip(cfg, 0, max_q - 1)
    oh_cfg = jax.nn.one_hot(cfg, max_q, dtype=jnp.float32)
    oh_child = jax.nn.one_hot(child_col, r_max, dtype=jnp.float32)
    return oh_cfg.T @ oh_child


# ---------------------------------------------------------------------------
# Fused all-candidate sweep engine
# ---------------------------------------------------------------------------
#
# The FES candidate sweep for child y evaluates n families (Pa_y + {x}) at
# once.  The extended parent configuration factorizes,
#
#     cfg_x = (cfg0, X_x)        for every candidate x simultaneously,
#
# so instead of n per-candidate table builds the whole sweep is ONE joint
# contraction over the batched index (child value b, base config j0):
#
#     counts[b, j0, x*r_max + a] = #(child = b, cfg0 = j0, X_x = a)
#                                = OH(cfg0 | child=b)^T @ OH_all(data)
#
# r_max small (max_q, m) @ (m, n*r_max) matmuls (the Pallas kernel in
# repro/kernels/bdeu_sweep) or one segment-sum of the (m, n*r_max) one-hot
# (the jnp reference below).  The per-candidate (Q, R) table is the slice
# counts[:, :, x*r_max:(x+1)*r_max] with rows (j0, a) — an injective
# relabeling of the radix codes cfg0 * r_x + X_x, and BDeu depends only on
# the partition the codes induce, so the non-canonical order is exact.
# Rows with a >= r_x, j0 >= q0 or b >= r_y have zero counts and cancel
# exactly (lgamma(N + a) - lgamma(a) = 0 at N = 0): dense padding is exact.
#
# Roofline (paper scale n=400, m=5000, max_q=4096, r=4): the per-candidate
# loop issues n memory-bound builds with r_max=4 result columns (4/128 MXU
# lanes used); the fused sweep issues r_max MXU-shaped contractions with
# n*r_max = 1600 result columns, ~n/r_max = 100x fewer dispatches per child
# and ~full lane utilization — compute goes from latency-bound scatter/matmul
# dribble to a handful of dense GEMMs (2*m*max_q*n*r_max ~ 2.6e11 flop per
# child sweep, ~3 ms at 100 Tflop/s).

FUSED_IMPLS = ("fused", "fused_pallas")

# Every legal sweep backend.  Dispatch sites fall through to the segment
# engine for anything unrecognized, so entry points (GESConfig, sweeps.sweep)
# validate against this list up front — a typo'd impl (e.g. in the CI
# matrix's REPRO_COUNTS_IMPL) must fail loudly, not silently run "segment".
COUNTS_IMPLS = ("segment", "onehot", "pallas") + FUSED_IMPLS


def check_counts_impl(counts_impl: str) -> str:
    if counts_impl not in COUNTS_IMPLS:
        raise ValueError(
            f"unknown counts_impl {counts_impl!r}; valid: {COUNTS_IMPLS} "
            f"(did REPRO_COUNTS_IMPL or a config typo sneak through?)")
    return counts_impl

# Fused impls accelerate the *candidate sweeps* (insert + delete); everywhere a
# single family is scored (base scores, graph totals, the one family-table
# build of the fused delete sweep) they degrade to the matching per-family
# engine.
_SINGLE_IMPL = {"fused": "segment", "fused_pallas": "pallas"}


def single_impl(counts_impl: str) -> str:
    """Per-family counts engine backing a (possibly fused) counts_impl."""
    return _SINGLE_IMPL.get(counts_impl, counts_impl)


def _onehot_all(data: Array, r_max: int) -> Array:
    """(m, r_max*n) value-major padded one-hot of every data column (column
    a*n + x is [X_x == a]) — child-independent, so full sweeps hoist it out
    of the per-child map."""
    m, n = data.shape
    oh = jax.nn.one_hot(data, r_max, dtype=jnp.float32)            # (m, n, r)
    return oh.transpose(0, 2, 1).reshape(m, r_max * n)


def _sweep_counts_segment(cfg0: Array, child_col: Array, oh_all: Array,
                          max_q: int, r_max: int) -> Array:
    """Joint sweep counts (r_max, r_max, max_q, n) via one segment-sum.

    counts[b, a, j0, x] = #(child=b, X_x=a, cfg0=j0) — the layout of the
    bdeu_sweep Pallas kernel, which this jnp path mirrors; ``oh_all`` is the
    value-major (m, r_max*n) data one-hot from :func:`_onehot_all`.

    Sentinel rows (child = r_max, from the data-axis sharder's ragged-m
    padding) are routed to an explicit overflow segment and sliced off —
    bitwise-identical routing for in-range rows.
    """
    ok = (child_col >= 0) & (child_col < r_max)
    idx = jnp.where(ok, child_col * max_q + jnp.clip(cfg0, 0, max_q - 1),
                    r_max * max_q)
    counts = jax.ops.segment_sum(
        oh_all, idx, num_segments=r_max * max_q + 1)[: r_max * max_q]
    n = oh_all.shape[1] // r_max
    return counts.reshape(r_max, max_q, r_max, n).transpose(0, 2, 1, 3)


def fused_insert_scores(
    data: Array,
    arities: Array,
    child: Array,
    parent_mask: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "fused",
    oh_all: Array | None = None,
    pids: Array | None = None,
    data_axis_name: str | None = None,
) -> Array:
    """(n,) BDeu scores of ALL candidate families (Pa + {x}) for one child.

    One joint contraction replaces the n per-candidate table builds of the
    loop engine (see the section comment above for the factorized-config
    encoding and the exactness-by-cancellation argument).  Entry x holds
    score(child, Pa + {x}); candidates whose extended parent set overflows
    the static table bound (q0 * r_x > max_q) are -inf.  Entries at
    x == child or x already in Pa are scored with the duplicated slot
    (q = q0 * r_x) — garbage by convention; ``repro.core.sweeps`` masks them
    before any caller sees the column.

    ``pids``: optional (W,) candidate subset — the ring's restricted E_i.
    The W candidate data columns are gathered BEFORE the joint contraction,
    so the contraction width (and the (W, Q, R) score slab) scales with W,
    not n, and the return shape is (W,).

    ``oh_all``: optional pre-built :func:`_onehot_all` of ``data`` — full
    sweeps pass it so the child-independent one-hot is built once, not once
    per mapped child.  With ``pids`` the W candidate one-hot blocks are
    gathered out of it (a gather of a one-hot IS the one-hot of the gather,
    so this is exact), sparing the per-column rebuild on the restricted path.

    ``data_axis_name``: instance axis sharded over that mesh axis — each
    device contracts its m/d shard; one psum rebuilds the global joint
    counts before the (m-independent) BDeu reduction below.
    """
    cfg0, q0 = _slot_encode(data, arities, parent_mask)
    child_col = jnp.take(data, child, axis=1)
    cfg0c = jnp.clip(cfg0, 0, max_q - 1)
    if pids is None:
        data_c, ar_c = data, arities
    else:
        data_c = jnp.take(data, pids, axis=1)
        ar_c = jnp.take(arities, pids)
    w = data_c.shape[1]
    if counts_impl == "fused_pallas":
        from ..kernels.bdeu_sweep import sweep_counts, sweep_counts_restricted
        if pids is None:
            counts = sweep_counts(cfg0c, child_col, data,
                                  max_q=max_q, r_max=r_max,
                                  data_axis_name=data_axis_name)
        else:
            counts = sweep_counts_restricted(cfg0c, child_col, data, pids,
                                             max_q=max_q, r_max=r_max,
                                             data_axis_name=data_axis_name)
    else:
        if oh_all is not None and pids is not None:
            n = data.shape[1]
            cols = (jnp.arange(r_max, dtype=pids.dtype)[:, None] * n
                    + pids[None, :]).reshape(-1)
            oh_all = jnp.take(oh_all, cols, axis=1)
        elif oh_all is None:
            oh_all = _onehot_all(data_c, r_max)
        counts = _sweep_counts_segment(cfg0c, child_col, oh_all, max_q, r_max)
        counts = _psum_counts(counts, data_axis_name)
    # (b, a, j0, x) -> per-candidate tables (x, (j0, a), b)
    slab = counts.transpose(3, 2, 1, 0).reshape(w, max_q * r_max, r_max)
    q = q0.astype(jnp.float32) * ar_c.astype(jnp.float32)         # (w,)
    scores = _bdeu_from_counts(slab, q, arities[child], ess)
    log_q0 = jnp.sum(jnp.where(parent_mask,
                               jnp.log(arities.astype(jnp.float32)), 0.0))
    ok = (log_q0 + jnp.log(ar_c.astype(jnp.float32))
          ) <= jnp.log(jnp.float32(max_q)) + 1e-4
    return jnp.where(ok, scores, -jnp.inf)


def fused_delete_scores(
    data: Array,
    arities: Array,
    child: Array,
    parent_mask: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "fused",
    pids: Array | None = None,
    data_axis_name: str | None = None,
) -> Array:
    """(n,) BDeu scores of ALL candidate families (Pa - {x}) for one child,
    from ONE family-table build.

    The BES delete sweep needs counts(Pa - {x}) for every parent x.  Every
    one of those tables is a *marginalization* of the current-family table:
    with the radix encoding of :func:`_slot_encode` (slot x has place value
    low_x = prod_{i>x} ar_i), row j0 decomposes as

        j0 = (hi * ar_x + d_x) * low_x + lo ,

    and summing the child-conditional counts over the digit d_x yields the
    table of Pa - {x} at rows hi * low_x + lo — an injective relabeling of
    the reduced configs, and BDeu depends only on the partition the codes
    induce.  So the whole delete column is ONE (max_q, r_max) table build
    (O(m), the same cost as the base score) plus an O(n * max_q * r_max)
    segment-sum with no data re-counting, replacing the loop engine's n
    per-candidate builds.

    Entry x holds score(child, Pa - {x}); at x not in Pa the marginalization
    is the identity (phantom arity-1 slot), so the entry equals the current
    family's score — the loop engine's no-op convention.  Candidates whose
    *reduced* family still overflows max_q are -inf, the same per-candidate
    guard convention as :func:`local_score_masked`.  When the current family
    itself overflows (q0 > max_q — possible only on unguarded init graphs,
    e.g. ring-fusion unions), the finite entries are clip-corrupted, but the
    *delta* against the (-inf) base reproduces the loop engine's +/-inf
    column exactly, so greedy trajectories still agree.

    ``pids``: optional (W,) candidate subset (ring E_i) — only the W
    marginalization maps are built and the return shape is (W,).

    With ``counts_impl="fused_pallas"`` the table build and the
    marginalizations collapse into ONE Pallas kernel
    (``kernels/bdeu_sweep.delete_marginals``): the family table is
    accumulated in VMEM, each parent slot's marginal is formed there, and
    only the (S + 1) slot tables reach HBM — the w-wide marginal slab of the
    jnp path is never built.  Since a family has at most
    ``floor(log2(max_q))`` real (arity > 1) parents before it overflows the
    table bound (each multiplies q0 by at least 2), the kernel marginalizes
    that many slots; candidates that are not real parents read the
    base-family score off slot 0 (the identity marginalization, exactly this
    function's jnp no-op convention), and overflow-guarded families
    (q0 > max_q) only need the +/-inf *pattern* below, which the shared
    guard supplies.  Both paths reduce tables to scores with the same
    :func:`_bdeu_from_counts`, so identical tables score identically.

    ``data_axis_name``: instance axis sharded over that mesh axis.  The
    kernel marginalizes the *local* family table, so under data sharding
    ``"fused_pallas"`` routes to the two-step path (table build via the
    psum-able ``contingency_counts`` wrapper + jnp marginalization).
    """
    n = data.shape[1]
    cfg0, q0 = _slot_encode(data, arities, parent_mask)
    child_col = jnp.take(data, child, axis=1)
    cfg0c = jnp.clip(cfg0, 0, max_q - 1)

    slot_ar_full = jnp.where(parent_mask, arities, 1).astype(jnp.int32)  # (n,)
    # place value of slot x under the _slot_encode scan: prod_{i > x} ar_i
    low_full = jnp.concatenate(
        [jnp.cumprod(slot_ar_full[::-1])[::-1][1:], jnp.ones(1, jnp.int32)])
    if pids is None:
        slot_ar, low = slot_ar_full, low_full
    else:
        slot_ar = jnp.take(slot_ar_full, pids)
        low = jnp.take(low_full, pids)
    w = slot_ar.shape[0]

    if counts_impl == "fused_pallas" and data_axis_name is None:
        from ..kernels.bdeu_sweep import delete_marginals

        n_slots = max(1, min(n, max(int(max_q).bit_length() - 1, 1)))
        real = parent_mask & (arities > 1)               # identity slots skip
        rank = jnp.cumsum(real.astype(jnp.int32)) - 1
        # rank clamp only engages when q0 > max_q (2^(S+1) > max_q), where
        # finite values are garbage-by-convention and the guard below owns
        # the +/-inf pattern
        cand_slot_full = jnp.where(
            real, jnp.minimum(rank, n_slots - 1) + 1, 0).astype(jnp.int32)
        cand_slot = (cand_slot_full if pids is None
                     else jnp.take(cand_slot_full, pids))
        keys = jnp.where(real, jnp.arange(n, dtype=jnp.int32), n)
        slot_ids = jnp.sort(keys)[:n_slots]              # first S real parents
        live = slot_ids < n
        ids_c = jnp.minimum(slot_ids, n - 1)
        ar_s = jnp.where(live, jnp.take(slot_ar_full, ids_c), 1)
        low_s = jnp.where(live, jnp.take(low_full, ids_c), 1)
        # slot 0 is the family table itself; slot s + 1 sums out parent s
        tables = delete_marginals(cfg0c, child_col, ar_s, low_s,
                                  max_q=max_q, r_max=r_max)
        q_slots = jnp.concatenate([q0[None], q0 // ar_s])
        slot_scores = _bdeu_from_counts(tables, q_slots, arities[child], ess)
        scores = jnp.take(slot_scores, cand_slot)
    else:
        impl = single_impl(counts_impl)
        if impl == "onehot":
            counts0 = _dense_counts_onehot(cfg0c, child_col, r_max, max_q)
            counts0 = _psum_counts(counts0, data_axis_name)
        elif impl == "pallas":
            from ..kernels.bdeu_count import contingency_counts
            counts0 = contingency_counts(cfg0c, child_col,
                                         max_q=max_q, r_max=r_max,
                                         data_axis_name=data_axis_name)
        else:
            counts0 = _dense_counts_segment(cfg0c, child_col, r_max, max_q)
            counts0 = _psum_counts(counts0, data_axis_name)

        j0 = jnp.arange(max_q, dtype=jnp.int32)[None, :]             # (1, Q)
        low_c = low[:, None]
        hi = j0 // (low_c * slot_ar[:, None])
        lo = j0 % low_c
        mapped = hi * low_c + lo                                     # (w, Q)
        flat = (jnp.arange(w, dtype=jnp.int32)[:, None] * max_q + mapped)
        tiled = jnp.broadcast_to(counts0, (w,) + counts0.shape)
        slab = jax.ops.segment_sum(
            tiled.reshape(w * max_q, r_max), flat.reshape(-1),
            num_segments=w * max_q).reshape(w, max_q, r_max)

        q_del = (q0 // slot_ar).astype(jnp.float32)                  # (w,)
        scores = _bdeu_from_counts(slab, q_del, arities[child], ess)

    log_q0 = jnp.sum(jnp.where(parent_mask,
                               jnp.log(arities.astype(jnp.float32)), 0.0))
    ok = (log_q0 - jnp.log(slot_ar.astype(jnp.float32))
          ) <= jnp.log(jnp.float32(max_q)) + 1e-4
    return jnp.where(ok, scores, -jnp.inf)


def loop_insert_scores(
    data: Array,
    arities: Array,
    child: Array,
    parent_mask: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "segment",
    pids: Array | None = None,
    data_axis_name: str | None = None,
) -> Array:
    """Loop-engine insert sweep with INCREMENTAL config encoding: scores of
    the candidate families (Pa + {x}) for one child, one contingency-table
    build per candidate.

    The parent-set radix code cfg0 is built once per child; each candidate
    extends it as ``cfg0 * r_x + X_x`` — O(m) per candidate instead of
    re-encoding all n slots.  BDeu depends only on the partition the codes
    induce (any injective relabeling gives identical counts), so the
    non-canonical code order is exact.

    This is THE loop-engine insert-column primitive: both the full (n, n)
    delta matrix (bdeu._deltas_impl) and the per-column/restricted sweeps
    (core/sweeps.sweep_column_body) call it, so full-n and pid-restricted
    programs see BITWISE-identical candidate scores — which the compiled
    ring's full-n tie-breaking argmax relies on (ges._masked_argmax_mapped).

    ``pids``: optional (W,) candidate subset — only those candidates are
    scored and the return shape is (W,).  Entries at x == child or x already
    in Pa are scored with the duplicated slot (garbage by convention, masked
    by callers); candidates whose extended family overflows max_q are -inf.

    ``data_axis_name``: instance axis sharded — each per-candidate table is
    psum'd over the mesh axis before its reduction (the vmap batches all W
    psums into one collective).
    """
    impl = single_impl(counts_impl)
    cfg0, q0 = _slot_encode(data, arities, parent_mask)
    child_col = jnp.take(data, child, axis=1)
    r = arities[child]
    log_q0 = jnp.sum(jnp.where(parent_mask,
                               jnp.log(arities.astype(jnp.float32)), 0.0))
    log_max = jnp.log(jnp.float32(max_q)) + 1e-4
    cand = (jnp.arange(data.shape[1], dtype=jnp.int32) if pids is None
            else pids)

    def per_parent(x):
        ar_x = arities[x]
        cfg = cfg0 * ar_x + jnp.take(data, x, axis=1)
        q = q0 * ar_x
        cfgc = jnp.clip(cfg, 0, max_q - 1)
        if impl == "onehot":
            counts = _dense_counts_onehot(cfgc, child_col, r_max, max_q)
            counts = _psum_counts(counts, data_axis_name)
        elif impl == "pallas":
            from ..kernels.bdeu_count import contingency_counts
            counts = contingency_counts(cfgc, child_col,
                                        max_q=max_q, r_max=r_max,
                                        data_axis_name=data_axis_name)
        else:
            counts = _dense_counts_segment(cfgc, child_col, r_max, max_q)
            counts = _psum_counts(counts, data_axis_name)
        score = _bdeu_from_counts(counts, q, r, ess)
        ok = (log_q0 + jnp.log(arities[x].astype(jnp.float32))) <= log_max
        return jnp.where(ok, score, -jnp.inf)

    return jax.vmap(per_parent)(cand)


def local_score_masked(
    data: Array,
    arities: Array,
    child: Array,
    parent_mask: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "segment",
    data_axis_name: str | None = None,
) -> Array:
    """Jit-safe BDeu local score: child (scalar int), parent_mask (n,) bool.

    ``data_axis_name``: instance axis sharded — the family table is psum'd
    over that mesh axis before the (m-independent) reduction.
    """
    counts_impl = single_impl(counts_impl)
    cfg, q = _slot_encode(data, arities, parent_mask)
    child_col = jnp.take(data, child, axis=1)
    if counts_impl == "onehot":
        counts = _dense_counts_onehot(cfg, child_col, r_max, max_q)
        counts = _psum_counts(counts, data_axis_name)
    elif counts_impl == "pallas":
        from ..kernels.bdeu_count import contingency_counts
        counts = contingency_counts(
            jnp.clip(cfg, 0, max_q - 1), child_col, max_q=max_q, r_max=r_max,
            data_axis_name=data_axis_name)
    else:
        counts = _dense_counts_segment(cfg, child_col, r_max, max_q)
        counts = _psum_counts(counts, data_axis_name)
    r = arities[child]
    score = _bdeu_from_counts(counts, q, r, ess)
    # Dense-table overflow guard: if the true q exceeds the static table bound
    # the counts are invalid -> return -inf so greedy search never selects it.
    # (log-domain check; the int64 q itself can wrap for absurd parent sets.)
    log_q = jnp.sum(jnp.where(parent_mask, jnp.log(arities.astype(jnp.float32)), 0.0))
    ok = log_q <= jnp.log(jnp.float32(max_q)) + 1e-4
    return jnp.where(ok, score, -jnp.inf)


def family_scores_batch(
    data: Array,
    arities: Array,
    children: Array,
    parent_masks: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "segment",
    data_axis_name: str | None = None,
) -> Array:
    """vmapped local scores for a batch of (child, parent_mask) families."""
    fn = lambda c, pm: local_score_masked(
        data, arities, c, pm, ess, max_q, r_max, counts_impl,
        data_axis_name=data_axis_name
    )
    return jax.vmap(fn)(children, parent_masks)


def graph_score_jax(
    data: Array,
    arities: Array,
    adj: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "segment",
    data_axis_name: str | None = None,
) -> Array:
    """Total BDeu of a DAG (jit-safe): sum of all n local scores.

    Families whose true q exceeds ``max_q`` score -inf here (the compiled
    tables are max_q-wide by construction), whereas :func:`graph_score_np`
    reports the unguarded BDeu.  A fused init graph can hand GES such a
    family, and if BES never profits from deleting it the two engines then
    report different totals for the SAME final graph (the compiled one
    -inf) — score comparisons across engines must either avoid the guard
    (raise max_q) or compare finite entries only.  Worse, when the guard
    bites a base family but not its delete-reduced families, the compiled
    BES sees +inf deltas and deletes where the host engine (np-exact,
    unguarded local scores) sees the true negative delta and keeps —
    host-vs-compiled trajectory pins must therefore run with max_q above
    every family q the fused inits can produce."""
    n = adj.shape[0]
    children = jnp.arange(n, dtype=jnp.int32)
    masks = adj.astype(bool).T  # row y of masks = parents of y
    scores = family_scores_batch(
        data, arities, children, masks, ess, max_q, r_max, counts_impl,
        data_axis_name=data_axis_name
    )
    return scores.sum()


# ---------------------------------------------------------------------------
# Sweep-level primitives: all-candidate delta matrices (FES / BES)
# ---------------------------------------------------------------------------

def _deltas_impl(data, arities, adj, ess, max_q, r_max, counts_impl,
                 child_chunk, insert: bool,
                 axis_name=None, axis_size: int = 1,
                 data_axis_name=None):
    """Shared implementation of insert/delete delta matrices.

    The (n^2) candidate sweep would naively materialize (n, n, m) config
    intermediates — at paper scale (n~1000, m=5000) that is tens of GB.  We
    bound peak memory by mapping *sequentially* over chunks of children with
    ``lax.map`` (batched vmap inside each chunk):  peak = chunk * n * m.

    ``axis_name``: inside shard_map, split the child sweep across that mesh
    axis (the paper's "inner calculations in parallel" as scoring-TP): each
    device scores n/axis_size children, then an all-gather reassembles the
    (n, n) delta matrix.

    ``data_axis_name``: ORTHOGONAL second mesh axis sharding the instance
    (m) axis — each device contracts its m/d one-hot shard and the count
    tables are psum'd before every BDeu reduction.  Composes freely with the
    scoring-TP child split above (2-D mesh: children x instances).
    """
    n = adj.shape[0]
    children = jnp.arange(n, dtype=jnp.int32)
    base_masks = adj.astype(bool).T  # (n_child, n): row y = parents of y

    # Hoisted out of the per-child map: the data one-hot is child-independent.
    oh_all = (_onehot_all(data, r_max)
              if insert and counts_impl == "fused" else None)

    def per_child_insert_fused(args):
        """Fused insert sweep: ALL n candidate tables from one joint
        contraction (see fused_insert_scores) — the whole per-child loop
        below collapses to a single r_max-batched count build plus one
        vectorized (n, Q, R) -> (n,) BDeu reduction."""
        y, pm, b = args
        return fused_insert_scores(
            data, arities, y, pm, ess, max_q, r_max, counts_impl,
            oh_all=oh_all, data_axis_name=data_axis_name) - b

    def per_child_insert_loop(args):
        """Insert sweep via the ONE loop-engine primitive
        (:func:`loop_insert_scores`): incremental config encoding, one
        table build per candidate — shared with the per-column/restricted
        sweeps so full-n and restricted programs agree bitwise."""
        y, pm, b = args
        return loop_insert_scores(
            data, arities, y, pm, ess, max_q, r_max, counts_impl,
            data_axis_name=data_axis_name) - b

    def per_child_delete_fused(args):
        """Fused delete sweep: ONE family-table build per child; every
        candidate table is a marginalization of it over one parent slot
        (see fused_delete_scores) — zero re-counting for the whole column."""
        y, pm, b = args
        return fused_delete_scores(
            data, arities, y, pm, ess, max_q, r_max, counts_impl,
            data_axis_name=data_axis_name) - b

    def per_child_delete(args):
        y, pm, b = args

        def per_parent(x):
            new_pm = pm.at[x].set(False)
            return local_score_masked(
                data, arities, y, new_pm, ess, max_q, r_max, counts_impl,
                data_axis_name=data_axis_name
            )
        return jax.vmap(per_parent)(jnp.arange(n, dtype=jnp.int32)) - b

    if insert:
        per_child = (per_child_insert_fused if counts_impl in FUSED_IMPLS
                     else per_child_insert_loop)
    else:
        per_child = (per_child_delete_fused if counts_impl in FUSED_IMPLS
                     else per_child_delete)
    if counts_impl in FUSED_IMPLS and child_chunk is None:
        # A fused child sweep materializes a per-child slab — insert: the
        # (r_max, r_max, max_q, n) joint counts; delete: the (n, max_q,
        # r_max) marginalization stack.  Map children sequentially so that
        # slab exists for one child at a time instead of vmapping it n-wide
        # (n^2-scale peak memory: gigabytes at paper scale).
        child_chunk = 1

    def base_for(ch, masks):
        return family_scores_batch(
            data, arities, ch, masks, ess, max_q, r_max, counts_impl,
            data_axis_name=data_axis_name)

    if axis_name is not None:
        per = -(-n // axis_size)                    # children per device
        i = jax.lax.axis_index(axis_name)
        ids = jnp.clip(i * per + jnp.arange(per), 0, n - 1).astype(jnp.int32)
        masks_l = base_masks[ids]
        base_l = base_for(ids, masks_l)
        scores_l = jax.lax.map(per_child, (ids, masks_l, base_l),
                               batch_size=min(child_chunk or per, per))
        scores = jax.lax.all_gather(scores_l, axis_name, axis=0,
                                    tiled=True)[:n]     # (y, x)
        return scores.T

    base = base_for(children, base_masks)
    if child_chunk is None or child_chunk >= n:
        scores_xy = jax.vmap(per_child)((children, base_masks, base))
    else:
        scores_xy = jax.lax.map(
            per_child, (children, base_masks, base), batch_size=child_chunk
        )
    return scores_xy.T


def insert_deltas(
    data: Array,
    arities: Array,
    adj: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "segment",
    child_chunk: int | None = None,
    axis_name=None,
    axis_size: int = 1,
    data_axis_name=None,
) -> Array:
    """Delta matrix D[x, y] = score(y, Pa_y + {x}) - score(y, Pa_y) for all pairs.

    Invalid candidates (x == y, existing edges, parent-set overflow w.r.t.
    max_q) are NOT masked here — callers apply masks (allowed-edge set E_i,
    acyclicity, cGES-L limits).  Shape (n, n), jit-safe.
    """
    return _deltas_impl(data, arities, adj, ess, max_q, r_max, counts_impl,
                        child_chunk, insert=True,
                        axis_name=axis_name, axis_size=axis_size,
                        data_axis_name=data_axis_name)


def delete_deltas(
    data: Array,
    arities: Array,
    adj: Array,
    ess: float,
    max_q: int,
    r_max: int,
    counts_impl: str = "segment",
    child_chunk: int | None = None,
    axis_name=None,
    axis_size: int = 1,
    data_axis_name=None,
) -> Array:
    """Delta matrix D[x, y] = score(y, Pa_y - {x}) - score(y, Pa_y).

    Only meaningful where adj[x, y] == 1; other entries are garbage and must
    be masked by the caller.
    """
    return _deltas_impl(data, arities, adj, ess, max_q, r_max, counts_impl,
                        child_chunk, insert=False,
                        axis_name=axis_name, axis_size=axis_size,
                        data_axis_name=data_axis_name)


def pairwise_similarity_jax(
    data: Array, arities: Array, ess: float, r_max: int
) -> Array:
    """Jit-safe Eq. (4) similarity matrix (for edge partitioning)."""
    n = data.shape[1]
    empty = jnp.zeros((n, n), dtype=jnp.int8)
    d = insert_deltas(data, arities, empty, ess, max_q=r_max, r_max=r_max)
    s = 0.5 * (d + d.T)
    return s - jnp.diag(jnp.diag(s))


def pairwise_similarity_fast(
    data: np.ndarray, arities: np.ndarray, ess: float = 10.0
) -> np.ndarray:
    """All-pairs Eq. (4) similarity from ONE contingency matmul.

    Every 2-way table N[i,a,j,b] = #(X_i=a AND X_j=b) is a block of
    OH(data)^T @ OH(data) with OH the (m, n*r_max) padded one-hot — the same
    MXU-native contraction as the ``bdeu_count`` Pallas kernel, batched over
    all n^2 pairs at once.  Replaces n^2 independent per-pair scans:
    flops = m*(n*r_max)^2 (one matmul) instead of n^2 scoring dispatches.

    Exactness: padded states/rows have zero counts and their BDeu terms
    cancel (lgamma(0+a) - lgamma(a) = 0), so the padded algebra is exact.
    """
    m, n = data.shape
    r_max = int(arities.max())
    # one-hot (m, n*r_max); column i*r_max+a  <->  (X_i == a)
    oh = np.zeros((m, n * r_max), dtype=np.float32)
    cols = (np.arange(n)[None, :] * r_max + data).astype(np.int64)
    np.put_along_axis(oh.reshape(m, -1), cols, 1.0, axis=1)
    counts = (oh.T @ oh).reshape(n, r_max, n, r_max).astype(np.float64)

    r = arities.astype(np.float64)                       # (n,)
    # child i given parent j:  q = r_j, r = r_i
    q_ji = r[None, :]                                    # Q[i, j] = r_j
    r_ii = r[:, None]
    a_j = ess / q_ji                                     # (n, n)
    a_jk = ess / (q_ji * r_ii)
    # N[j_state, i_state] for (child i, parent j) is counts[j, :, i, :]
    njk = counts.transpose(2, 0, 1, 3)                   # (i, j, a_j, b_i)
    nj = njk.sum(axis=3)                                 # (i, j, a_j)
    term_j = (lgamma_np(a_j)[..., None] - lgamma_np(nj + a_j[..., None]))
    term_jk = (lgamma_np(njk + a_jk[..., None, None])
               - lgamma_np(np.broadcast_to(a_jk[..., None, None], njk.shape)))
    with_parent = term_j.sum(axis=2) + term_jk.sum(axis=(2, 3))  # (i, j)

    # base: child i with no parent (q = 1)
    ni = np.stack([counts[i, :, i, :].diagonal() for i in range(n)])  # (n, r)
    b_j = ess
    b_jk = ess / r
    base = (lgamma_np(np.full(n, b_j)) - lgamma_np(ni.sum(1) + b_j)
            + (lgamma_np(ni + b_jk[:, None])
               - lgamma_np(np.broadcast_to(b_jk[:, None], ni.shape))).sum(1))

    d = with_parent - base[:, None]                      # s(X_i <- X_j)
    s = 0.5 * (d + d.T)
    np.fill_diagonal(s, 0.0)
    return s
