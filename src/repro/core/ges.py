"""GES — Greedy Equivalence Search (greedy-FES variant of Alonso-Barba et al.
2013, the exact variant the cGES paper uses as its local learner; see paper
§2.2) with the BES stage intact.

Search is performed in DAG space with the score-equivalent BDeu metric:
* FES: repeatedly apply the best positive single-edge insertion.
* BES: repeatedly apply the best positive single-edge deletion.

Both stages can be restricted to an ``allowed`` edge mask (the E_i subsets of
cGES) and FES can be capped at ``add_limit`` insertions (cGES-L).

Two drivers with identical greedy trajectories:

* :func:`ges_host` — Python loop + jitted *column* rescoring (the incremental
  trick: after touching child y only column y of the delta cache changes).
  This is the "parallel GES" control algorithm of the paper — the candidate
  sweep is the parallel part, here a single batched tensor op.
* :func:`ges_jit` — the whole FES+BES search as one jit-compiled
  ``lax.while_loop`` program (fixed shapes), used inside the shard_map ring.

All candidate rescoring — FES insert columns, BES delete columns, restricted
E_i subsets, full delta matrices — goes through the unified engine in
:mod:`repro.core.sweeps` (``sweep(kind="insert"|"delete", pids=...)``), which
dispatches to the loop / fused-jnp / fused-Pallas backend named by
``GESConfig.counts_impl``.

Both drivers pay W-wide restricted sweeps when given the E_i candidate
table: :func:`ges_host` gathers each column down to its ``pids`` subset, and
:func:`ges_jit` threads a static (n, W) ``pid_table`` through its whole
``lax.while_loop`` program — delta state, argmax, apply and incremental
rescoring all live in (W, n) index space, so the compiled ring's per-round
cost tracks W = |E_i|, not n (the paper's core cost argument, end-to-end
compiled).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import zlib
from functools import lru_cache, partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from . import bdeu, score_cache
from .dag import closure_after_edge, transitive_closure, transitive_closure_np
from .partition import pid_table_from_allowed
from .sweeps import (DATA_AXIS, KIND_CODES, _data_mesh, pad_data_rows,
                     sweep, sweep_column_body,
                     sweep_column_cached, sweep_matrix_body,
                     sweep_matrix_restricted_body)

Array = jax.Array
NEG_INF = -jnp.inf


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "0").lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass(frozen=True)
class GESConfig:
    ess: float = 10.0
    max_parents: int = 6          # static parent-set bound for the device engine
    max_q: int = 4096             # dense contingency-table row bound
    # per-family loop engines: "segment" | "onehot" | "pallas";
    # fused sweep engines (insert: one contraction per child; delete: one
    # family-table build per child — not n either way):
    # "fused" (jnp) | "fused_pallas" (kernels/bdeu_sweep + bdeu_count).
    # The default honours REPRO_COUNTS_IMPL so CI can run the whole tier-1
    # suite under an alternate backend (the fused CI legs).  default_factory,
    # not a plain default: a dataclass default is bound once at class
    # creation, which would silently ignore the env var whenever it is set
    # after ``import repro`` (regression-tested).
    counts_impl: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_COUNTS_IMPL", "segment"))
    tol: float = 1e-9             # minimum improvement to keep going
    incremental: bool = True      # column-cached delta rescoring
    child_chunk: Optional[int] = None  # sequential chunking of full sweeps
    # Data-axis sharding for the HOST driver's sweeps: shard the instance
    # axis over this many devices (sweeps.sweep(data_shards=...)); results
    # are table-identical to 1 (regression-tested).  The compiled ring takes
    # its data axis from RingSpec instead (2-D ring x data mesh).
    data_shards: int = 1
    # Persistent device-resident family-score cache (core/score_cache):
    # memoises masked score columns across GES iterations, rounds and ring
    # members with prioritized eviction; trajectories stay bitwise-identical
    # to uncached.  Env-defaulted like counts_impl (read at call time) so a
    # CI leg can flip the whole suite with REPRO_FAMILY_CACHE=1.
    family_cache: bool = dataclasses.field(
        default_factory=lambda: _env_flag("REPRO_FAMILY_CACHE"))
    cache_capacity: int = 1024    # slots (columns) in the family-score cache

    def __post_init__(self):
        # Fail loudly on unknown backends: the dispatch chains fall through
        # to "segment", so a typo (config or REPRO_COUNTS_IMPL) would
        # otherwise silently run the wrong engine.
        bdeu.check_counts_impl(self.counts_impl)
        if self.data_shards < 1:
            raise ValueError(f"data_shards must be >= 1, got {self.data_shards}")
        if self.cache_capacity < 1:
            raise ValueError(
                f"cache_capacity must be >= 1, got {self.cache_capacity}")

    def static_key(self):
        return (self.ess, self.max_parents, self.max_q, self.counts_impl,
                self.tol, self.incremental, self.child_chunk,
                self.data_shards, self.family_cache, self.cache_capacity)


# ---------------------------------------------------------------------------
# Column-level delta rescoring — all of it goes through core/sweeps.sweep:
# one API, kind="insert"|"delete", optional pids restriction, engine-masked
# columns identical under the loop and fused backends.
# ---------------------------------------------------------------------------

# Score-equivalent insertions.  When Pa_x == Pa_y, inserting x -> y and
# y -> x give Markov-equivalent DAGs, so BDeu (score-equivalent) assigns them
# EXACTLY equal deltas; computed in f32 they differ only by rounding, and the
# rounding differs between count backends, XLA versions and devices.  Both
# GES loops therefore let the argmax pick the pair by value and then fix its
# direction by rule — the lower full-n flat index, min(x, y) -> max(x, y) —
# whenever the reverse insertion is also legal.  Trajectories then agree
# across engines instead of following rounding noise.

def _canonical_insert_np(x: int, y: int, adj: np.ndarray,
                         valid: np.ndarray):
    if x > y and valid[y, x] and np.array_equal(adj[:, x], adj[:, y]):
        return y, x
    return x, y


def _q_guard_np(adj: np.ndarray, arities: np.ndarray, max_q: int) -> np.ndarray:
    """Boolean (n, n) matrix: True where adding x->y keeps q_y <= max_q."""
    log_r = np.log(arities.astype(np.float64))
    log_q = adj.astype(np.float64).T @ log_r  # (n,) current log q per child
    return (log_q[None, :] + log_r[:, None]) <= np.log(max_q) + 1e-9


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GESResult:
    adj: np.ndarray
    score: float
    n_inserts: int
    n_deletes: int
    n_score_evals: int   # machine-independent cost counter (paper's CPU-time proxy)


def trace_steps(rnd: int, member: int, n_ins: int, n_del: int) -> None:
    """Write the ``ges.steps`` counter to the profiler trace: one instant
    event with the insertions and deletions ring member ``member`` applied
    in round ``rnd`` (both from 0).  Costs about a microsecond when no
    profiler runs."""
    with jax.profiler.TraceAnnotation(
            "ges.steps", round=int(rnd), member=int(member),
            inserts=int(n_ins), deletes=int(n_del)):
        pass


def trace_q_buckets(rnd: int, member: int, counts, max_q: int) -> None:
    """Write the ``ges.q_buckets`` counter to the profiler trace: one
    instant event with the column reductions one ``ges_jit`` call of ring
    member ``member`` made in round ``rnd``, per row bucket of
    ``bdeu.q_ladder(max_q)``: stat ``q<rows>`` counts the columns reduced
    over ``rows`` rows (``q16``, ``q64``, ...).  Member -1 is the fine-tune
    after the last round."""
    stats = {f"q{rows}": int(c)
             for rows, c in zip(bdeu.q_ladder(max_q), counts)}
    with jax.profiler.TraceAnnotation(
            "ges.q_buckets", round=int(rnd), member=int(member), **stats):
        pass


# Device-resident per-dataset arrays, cached across rounds: the host driver
# used to re-upload the (m, n) code array (and rebuild every derived one-hot
# from scratch on device) in EVERY ges_host call, although cges/ring_rounds
# call it with the same dataset dozens of times.  Content-addressed (sha1 of
# the bytes), so id-reuse can never alias two datasets; small and bounded.
_DEVICE_DATA_CACHE: dict = {}
_DEVICE_DATA_CAP = 8


def device_data(data: np.ndarray, arities: np.ndarray):
    """(data_j, ar_j) int32 device arrays for a host dataset, cached by
    content so repeated ges_host calls (cges rounds, ring driving) reuse the
    resident copies instead of re-transferring per call."""
    key = (hashlib.sha1(np.ascontiguousarray(data).tobytes()).digest(),
           hashlib.sha1(np.ascontiguousarray(arities).tobytes()).digest(),
           data.shape)
    hit = _DEVICE_DATA_CACHE.get(key)
    if hit is None:
        if len(_DEVICE_DATA_CACHE) >= _DEVICE_DATA_CAP:
            _DEVICE_DATA_CACHE.clear()
        hit = (jnp.asarray(data.astype(np.int32)),
               jnp.asarray(arities.astype(np.int32)))
        _DEVICE_DATA_CACHE[key] = hit
    return hit


class DeviceFamilyCache:
    """Mutable host handle to a device-resident family-score cache
    (:mod:`repro.core.score_cache`) for the HOST driver.

    Columns are cached in full-n scattered form (width n, -inf outside the
    restriction), so ONE handle is shared across cGES members with different
    E_i widths, across rounds, and by the unrestricted fine-tune; the scope
    word (crc32 of the allowed column) keeps differently-restricted columns
    from aliasing.  ``state`` is an immutable pytree — ges_host replaces it
    after every probe/insert, which is what makes the cache persist across
    calls.
    """

    def __init__(self, n_vars: int, capacity: int = 1024):
        self.n_vars = int(n_vars)
        self.state = score_cache.init(n_vars, n_vars, capacity)

    def stats(self) -> dict:
        return score_cache.stats(self.state)


def _scope_word(allowed_col: np.ndarray) -> int:
    """int32 scope for one column's allowed-candidate subset (crc32)."""
    v = zlib.crc32(np.ascontiguousarray(allowed_col).tobytes())
    return v - (1 << 32) if v >= (1 << 31) else v


class ScoreCache:
    """Cross-call delta-column cache — the host mirror of the paper's
    'concurrent safe data structure' that all ring processes share.

    Keyed by (kind, child, parent-set bytes); each hit saves n local-score
    evaluations.  A single instance is shared by all cGES processes across
    all ring rounds.
    """

    def __init__(self):
        self._store: dict = {}
        self.hits = 0
        self.misses = 0

    def column(self, kind: str, y: int, adj: np.ndarray, compute,
               scope: bytes = b"") -> np.ndarray:
        """``scope`` must identify the allowed-candidate subset the column
        was computed under (columns are -inf outside it): processes with
        different E_i may NOT share entries, or a restricted column would
        leak into another process / the unrestricted fine-tune."""
        key = (kind, y, scope, adj[:, y].tobytes())
        col = self._store.get(key)
        if col is None:
            self.misses += 1
            col = compute()
            self._store[key] = col
        else:
            self.hits += 1
        return col


def ges_host(
    data: np.ndarray,
    arities: np.ndarray,
    init_adj: Optional[np.ndarray] = None,
    allowed: Optional[np.ndarray] = None,
    add_limit: Optional[int] = None,
    config: Optional[GESConfig] = None,
    phases: str = "both",            # "fes" | "bes" | "both"
    cache: Optional[ScoreCache] = None,
    family_cache: Optional[DeviceFamilyCache] = None,
) -> GESResult:
    """Greedy FES+BES on host with jit-batched column rescoring.

    ``family_cache``: optional shared :class:`DeviceFamilyCache` — the
    device-resident persistent column cache (auto-created per call when
    ``config.family_cache`` is set and none is passed; cges passes one
    handle so entries persist across members, rounds and the fine-tune).
    It REPLACES the host-dict ``cache`` layer when present (both are exact
    and keyed identically — stacking them would starve the device cache).
    ``config.data_shards > 1`` shards every sweep's instance axis
    (sweeps.sweep(data_shards=...)); both knobs leave trajectories
    bitwise-identical.
    """
    m, n = data.shape
    # built per call, not bound at import — honours REPRO_COUNTS_IMPL set
    # after ``import repro`` (see GESConfig.counts_impl)
    cfg = config if config is not None else GESConfig()
    r_max = int(arities.max())
    adj = (np.zeros((n, n), dtype=np.int8) if init_adj is None
           else init_adj.astype(np.int8).copy())
    allowed_np = (np.ones((n, n), dtype=bool) if allowed is None
                  else allowed.astype(bool))
    np.fill_diagonal(allowed_np, False)

    data_j, ar_j = device_data(data, arities)
    if family_cache is None and cfg.family_cache:
        family_cache = DeviceFamilyCache(n, cfg.cache_capacity)
    if family_cache is not None and family_cache.n_vars != n:
        raise ValueError(
            f"family_cache was built for n={family_cache.n_vars} variables, "
            f"got a {n}-variable problem")
    scope_words = [_scope_word(allowed_np[:, y]) for y in range(n)]

    evals = 0

    # Restricted-subset column scoring: each column y only evaluates its
    # allowed candidates (W = max column occupancy of E_i, padded for static
    # jit shapes).  This is where the ring's speedup physically comes from —
    # a process pays |E_i|/n per column, not n.
    allowed_cost = allowed_np.sum(axis=0)
    pid_table = pid_table_from_allowed(allowed_np)
    pid_j = jnp.asarray(pid_table)

    def _scatter(y, vals):
        col = np.full(n, -np.inf)
        ids = pid_table[y]
        col[ids] = np.asarray(vals)
        col[y] = -np.inf                     # self-pad stays invalid
        return col

    def _col(kind, cache_key, a, y, n_evals):
        nonlocal evals

        def compute():
            nonlocal evals
            evals += n_evals
            vals = sweep(data_j, ar_j, jnp.asarray(a), kind=kind, y=y,
                         pids=pid_j[y], ess=cfg.ess, max_q=cfg.max_q,
                         r_max=r_max, counts_impl=cfg.counts_impl,
                         data_shards=cfg.data_shards)
            return _scatter(y, vals)

        def compute_device_cached():
            # Persistent device cache: probe answers hit/miss (refreshing
            # recency on device); only a miss pays the sweep, whose column
            # is then inserted with prioritized eviction.  The key is exact
            # (kind, y, parents, scope=crc32(allowed column)), so the
            # returned column is bitwise the one compute() would produce.
            fc = family_cache
            code = KIND_CODES[kind]
            pm = jnp.asarray(a[:, y] > 0)
            hit, col, fc.state = score_cache._probe_jit(
                fc.state, code, jnp.int32(y), pm, jnp.int32(scope_words[y]))
            if bool(hit):
                return np.asarray(col, dtype=np.float64)
            res = compute()
            fc.state = score_cache._insert_jit(
                fc.state, code, jnp.int32(y), pm, jnp.int32(scope_words[y]),
                jnp.asarray(res, dtype=jnp.float32))
            return res

        # The device cache REPLACES the host-dict layer (both are exact and
        # keyed identically, so a dict in front would absorb every hit and
        # the bounded device-resident cache would only ever see first-time
        # keys); either layer alone leaves trajectories identical.
        if family_cache is not None:
            return compute_device_cached()
        if cache is not None:
            return cache.column(cache_key, y, a, compute,
                                scope=allowed_np[:, y].tobytes())
        return compute()

    def ins_col(a, y):
        return _col("insert", "ins", a, y, int(allowed_cost[y]))

    def del_col(a, y):
        return _col("delete", "del", a, y,
                    int(np.sum(allowed_np[:, y] & (a[:, y] > 0))))

    n_ins = 0
    n_del = 0
    # Partition-restricted sweeps (the ring's whole point): a process whose
    # E_i excludes column y never scores it — the vectorized sweep mirrors
    # the paper's task pool by skipping empty columns outright.
    col_allowed = allowed_np.any(axis=0)
    NEG = np.full(n, -np.inf)

    # ---------------- FES ----------------
    if phases in ("fes", "both"):
        reach = transitive_closure_np(adj.astype(bool))
        D = np.stack([ins_col(adj, y) if col_allowed[y] else NEG
                      for y in range(n)], axis=1)            # (x, y)
        while True:
            if add_limit is not None and n_ins >= add_limit:
                break
            pa_count = adj.sum(axis=0)
            valid = (allowed_np & ~adj.astype(bool) & ~reach.T
                     & (pa_count[None, :] < cfg.max_parents)
                     & _q_guard_np(adj, arities, cfg.max_q))
            masked = np.where(valid, D, -np.inf)
            x, y = np.unravel_index(np.argmax(masked), masked.shape)
            if not np.isfinite(masked[x, y]) or masked[x, y] <= cfg.tol:
                break
            x, y = _canonical_insert_np(int(x), int(y), adj, valid)
            adj[x, y] = 1
            reach = closure_after_edge(reach, int(x), int(y))
            n_ins += 1
            D[:, y] = ins_col(adj, y)

    # ---------------- BES ----------------
    if phases in ("bes", "both"):
        del_cols = (adj.astype(bool) & allowed_np).any(axis=0)
        D = np.stack([del_col(adj, y) if del_cols[y] else NEG
                      for y in range(n)], axis=1)
        while True:
            valid = adj.astype(bool) & allowed_np
            masked = np.where(valid, D, -np.inf)
            x, y = np.unravel_index(np.argmax(masked), masked.shape)
            if not np.isfinite(masked[x, y]) or masked[x, y] <= cfg.tol:
                break
            adj[x, y] = 0
            n_del += 1
            D[:, y] = del_col(adj, y)

    score = bdeu.graph_score_np(data, arities, adj, cfg.ess)
    return GESResult(adj=adj, score=score, n_inserts=n_ins, n_deletes=n_del,
                     n_score_evals=evals)


# ---------------------------------------------------------------------------
# Fully-jitted driver (device engine, used inside the shard_map ring)
# ---------------------------------------------------------------------------

def _masked_argmax(mat: Array):
    """Return (flat_idx, value) of the max over a (n, n) matrix."""
    flat = mat.reshape(-1)
    idx = jnp.argmax(flat)
    return idx, flat[idx]


def _canonical_insert(x, y, adj, reverse_ok):
    """Traced twin of :func:`_canonical_insert_np`: swap to y -> x when that
    has the lower flat index, is legal, and Pa_x == Pa_y."""
    covered = jnp.all(adj[:, x] == adj[:, y])
    swap = (x > y) & covered & reverse_ok
    return jnp.where(swap, y, x), jnp.where(swap, x, y)


def _masked_argmax_mapped(mat: Array, key: Array, n: int):
    """Argmax over a (W, n) restricted matrix with FULL-N tie-breaking.

    ``key[w, y] = x*n + y`` is each entry's flat index in the (n, n) space.
    BDeu is score-equivalent, so exact delta ties (x -> y vs y -> x) are
    common, and jnp.argmax's first-maximum rule resolves them by position —
    which differs between (w, y) and (x, y) layouts.  Taking the minimum
    full-n key among the maxima reproduces the full-n path's tie-break
    exactly, which is what makes restricted and full-n-masked compiled
    trajectories identical (asserted by tests).
    """
    best = jnp.max(mat)
    idx = jnp.min(jnp.where(mat == best, key, jnp.int32(n * n)))
    return jnp.minimum(idx, jnp.int32(n * n - 1)), best


@partial(jax.jit, static_argnames=(
    "ess", "max_parents", "max_q", "r_max", "counts_impl", "tol", "incremental",
    "child_chunk"))
def _ges_jit_impl(data, arities, init_adj, allowed, add_limit, pid_table,
                  ess, max_parents, max_q, r_max, counts_impl, tol,
                  incremental, child_chunk, cache, cache_scope):
    return ges_jit_body(data, arities, init_adj, allowed, add_limit,
                        ess, max_parents, max_q, r_max, counts_impl, tol,
                        incremental, child_chunk, pid_table=pid_table,
                        cache=cache, cache_scope=cache_scope)


def ges_jit_body(data, arities, init_adj, allowed, add_limit,
                 ess, max_parents, max_q, r_max, counts_impl, tol,
                 incremental, child_chunk=None,
                 axis_model=None, axis_model_size: int = 1,
                 pid_table=None, data_axis_name=None,
                 cache=None, cache_scope=0):
    """Traceable (un-jitted) GES program — callable from inside shard_map.

    ``axis_model``: optional mesh axis over which the full candidate sweeps
    are split (scoring-TP inside a ring process; see bdeu._deltas_impl).

    ``pid_table``: optional static (n, W) candidate table (the ring's E_i,
    self-padded; see partition.pid_table_from_allowed).  When given, the
    ENTIRE program — the FES/BES initialization matrices, the while_loop's
    argmax/apply steps and the incremental column rescoring — runs in
    (W, n) index space: delta state is (W, n), winners map back through the
    table as ``x = pid_table[y, w]``, and every sweep pays W-wide cost.
    This is what makes the compiled ring's per-round cost track W = |E_i|
    instead of n.  ``pid_table=None`` keeps the full-n (n, n) path (the
    unrestricted fine-tune / plain-GES case).

    ``data_axis_name``: optional SECOND mesh axis sharding the instance (m)
    axis — every count build contracts the local m/d shard and psums (see
    core/sweeps, "Two ORTHOGONAL mesh axes").  The caller owns padding
    ragged m with sentinel rows (sweeps.pad_data_rows).

    ``cache``/``cache_scope``: optional persistent family-score cache state
    (score_cache.FamilyScoreCache, column width W if restricted else n).
    The FES/BES init matrices are then built column-by-column through the
    cache (lax.scan) and the incremental rescoring consults it inside the
    while_loop carries; the returned tuple gains the final cache state.
    Under a data axis the cache state is replicated across data-axis
    devices (identical psum'd columns -> identical evolution), so the
    hit/miss cond never diverges.

    Returns ``(adj, score, n_ins, n_del, q_buckets)`` plus the cache state
    when ``cache`` is given.  ``q_buckets`` is the ``ges.q_buckets``
    histogram: (len(bdeu.q_ladder(max_q)),) int32 column reductions per row
    bucket, from the same bucket function the fused sweeps switch on.  It
    depends on the parent sets alone, whatever ``counts_impl`` (the loop
    engines reduce all max_q rows all the same).  It counts the n columns
    of each initial FES and BES matrix and one column per applied step (n
    per step when not ``incremental``); a family-cache hit, which skips the
    reduction, still counts.
    """
    n = init_adj.shape[0]
    use_cache = cache is not None
    eye = jnp.eye(n, dtype=bool)
    allowed = allowed.astype(bool) & ~eye
    log_r = jnp.log(arities.astype(jnp.float32))
    log_max_q = jnp.log(jnp.float32(max_q)) + 1e-6
    restricted = pid_table is not None
    if restricted:
        x_of = pid_table.T                        # (W, n): x_of[w, y] = x
        ycols = jnp.arange(n, dtype=jnp.int32)[None, :]
        pid_key = x_of.astype(jnp.int32) * n + ycols   # full-n flat indices

        def gather_wy(mat):
            """(n, n) mask/matrix -> (W, n) entries at [pid_table[y, w], y]."""
            return mat[x_of, ycols]

    n_buckets = len(bdeu.q_ladder(max_q))

    def buckets_of(parent_mask):
        return bdeu.q_bucket(bdeu.parent_configs(arities, parent_mask), max_q)

    def matrix_buckets(adj):
        """q_buckets of a whole matrix sweep: one column per child."""
        return jnp.zeros(n_buckets, jnp.int32).at[
            buckets_of(adj.astype(bool).T)].add(1)

    def step_buckets(adj, y, applied):
        """q_buckets of the rescoring after an applied step on column y."""
        if not incremental:
            return matrix_buckets(adj) * applied.astype(jnp.int32)
        return jnp.zeros(n_buckets, jnp.int32).at[
            buckets_of(adj[:, y].astype(bool))].add(applied.astype(jnp.int32))

    def full_insert_D(adj):
        if restricted:
            return sweep_matrix_restricted_body(
                data, arities, adj, pid_table, ess, max_q, r_max,
                counts_impl, "insert", child_chunk,
                axis_name=axis_model, axis_size=axis_model_size,
                data_axis_name=data_axis_name)
        return sweep_matrix_body(data, arities, adj, ess, max_q, r_max,
                                 counts_impl, "insert", child_chunk,
                                 axis_name=axis_model,
                                 axis_size=axis_model_size,
                                 data_axis_name=data_axis_name)

    def full_delete_D(adj):
        if restricted:
            return sweep_matrix_restricted_body(
                data, arities, adj, pid_table, ess, max_q, r_max,
                counts_impl, "delete", child_chunk,
                axis_name=axis_model, axis_size=axis_model_size,
                data_axis_name=data_axis_name)
        return sweep_matrix_body(data, arities, adj, ess, max_q, r_max,
                                 counts_impl, "delete", child_chunk,
                                 axis_name=axis_model,
                                 axis_size=axis_model_size,
                                 data_axis_name=data_axis_name)

    def ins_col(adj, y):
        pids = pid_table[y] if restricted else None
        return sweep_column_body(data, arities, adj, y, pids, ess, max_q,
                                 r_max, counts_impl, "insert",
                                 data_axis_name=data_axis_name)

    def del_col(adj, y):
        pids = pid_table[y] if restricted else None
        return sweep_column_body(data, arities, adj, y, pids, ess, max_q,
                                 r_max, counts_impl, "delete",
                                 data_axis_name=data_axis_name)

    def col_cached(c, adj, y, kind):
        pids = pid_table[y] if restricted else None
        return sweep_column_cached(c, data, arities, adj, y, pids, ess,
                                   max_q, r_max, counts_impl, kind,
                                   scope=cache_scope,
                                   data_axis_name=data_axis_name)

    def cached_D(c, adj, kind):
        """Init matrix built column-by-column THROUGH the cache (lax.scan
        threads the cache state): a round whose graph already has column y's
        family cached skips that column's whole contraction.  Mirrors the
        uncached matrix bodies' child split under ``axis_model``."""
        ids = jnp.arange(n, dtype=jnp.int32)
        if axis_model is not None:
            per = -(-n // axis_model_size)
            i = jax.lax.axis_index(axis_model)
            ids = jnp.clip(i * per + jnp.arange(per), 0, n - 1).astype(
                jnp.int32)

        def scan_body(c, y):
            col, c = col_cached(c, adj, y, kind)
            return c, col

        c, cols = jax.lax.scan(scan_body, c, ids)            # (cnt, V)
        if axis_model is not None:
            cols = jax.lax.all_gather(cols, axis_model, axis=0,
                                      tiled=True)[:n]
        return cols.T, c

    # ---------------- FES ----------------
    def fes_cond(state):
        return ~state[4]

    def fes_body(state):
        adj, reach, D, n_ins, done, qb = state[:6]
        c = state[6] if use_cache else None
        pa_count = adj.sum(axis=0)
        log_q = jnp.dot(adj.astype(jnp.float32).T, log_r,
                        precision=jax.lax.Precision.HIGHEST)

        def insert_ok(a, b):
            """Full-n validity of inserting a -> b (the valid mask's entry)."""
            return (allowed[a, b] & (adj[a, b] == 0) & ~reach[b, a]
                    & (pa_count[b] < max_parents)
                    & (log_q[b] + log_r[a] <= log_max_q))

        if restricted:
            # same validity predicate as the full-n path, gathered into the
            # (W, n) index space: entry [w, y] tests x = pid_table[y, w] -> y
            valid = (gather_wy(allowed & ~adj.astype(bool))
                     & ~reach[ycols, x_of]          # == (~reach.T)[x, y]
                     & (pa_count[None, :] < max_parents)
                     & ((log_q[None, :] + log_r[x_of]) <= log_max_q))
        else:
            q_ok = (log_q[None, :] + log_r[:, None]) <= log_max_q
            valid = (allowed & ~adj.astype(bool) & ~reach.T
                     & (pa_count[None, :] < max_parents) & q_ok)
        masked = jnp.where(valid, D, NEG_INF)
        idx, best = (_masked_argmax_mapped(masked, pid_key, n) if restricted
                     else _masked_argmax(masked))
        x, y = idx // n, idx % n
        x, y = _canonical_insert(x, y, adj, insert_ok(y, x))
        do_apply = (best > tol) & (n_ins < add_limit)

        new_adj = adj.at[x, y].set(jnp.where(do_apply, 1, adj[x, y]))
        new_reach = jnp.where(do_apply, closure_after_edge(reach, x, y), reach)
        if incremental:
            if use_cache:
                new_col, c = col_cached(c, new_adj, y, "insert")
            else:
                new_col = ins_col(new_adj, y)
            new_D = jnp.where(do_apply, D.at[:, y].set(new_col), D)
        else:
            if use_cache:
                full_D, c = cached_D(c, new_adj, "insert")
            else:
                full_D = full_insert_D(new_adj)
            new_D = jnp.where(do_apply, full_D, D)
        out = (new_adj, new_reach, new_D,
               n_ins + do_apply.astype(jnp.int32), ~do_apply,
               qb + step_buckets(new_adj, y, do_apply))
        return out + (c,) if use_cache else out

    adj0 = init_adj.astype(jnp.int8)
    reach0 = transitive_closure(adj0.astype(bool))
    if use_cache:
        D0, cache = cached_D(cache, adj0, "insert")
    else:
        D0 = full_insert_D(adj0)
    state = (adj0, reach0, D0, jnp.int32(0), jnp.bool_(False),
             matrix_buckets(adj0))
    if use_cache:
        state = state + (cache,)
    fes_out = jax.lax.while_loop(fes_cond, fes_body, state)
    adj1, n_ins, qb = fes_out[0], fes_out[3], fes_out[5]
    if use_cache:
        cache = fes_out[6]

    # ---------------- BES ----------------
    def bes_cond(state):
        return ~state[3]

    def bes_body(state):
        adj, D, n_del, done, qb = state[:5]
        c = state[5] if use_cache else None
        valid = adj.astype(bool) & allowed
        if restricted:
            valid = gather_wy(valid)
        masked = jnp.where(valid, D, NEG_INF)
        idx, best = (_masked_argmax_mapped(masked, pid_key, n) if restricted
                     else _masked_argmax(masked))
        x, y = idx // n, idx % n
        do_apply = best > tol
        new_adj = adj.at[x, y].set(jnp.where(do_apply, 0, adj[x, y]))
        if incremental:
            if use_cache:
                new_col, c = col_cached(c, new_adj, y, "delete")
            else:
                new_col = del_col(new_adj, y)
            new_D = jnp.where(do_apply, D.at[:, y].set(new_col), D)
        else:
            if use_cache:
                full_D, c = cached_D(c, new_adj, "delete")
            else:
                full_D = full_delete_D(new_adj)
            new_D = jnp.where(do_apply, full_D, D)
        out = (new_adj, new_D, n_del + do_apply.astype(jnp.int32), ~do_apply,
               qb + step_buckets(new_adj, y, do_apply))
        return out + (c,) if use_cache else out

    if use_cache:
        D1, cache = cached_D(cache, adj1, "delete")
    else:
        D1 = full_delete_D(adj1)
    state = (adj1, D1, jnp.int32(0), jnp.bool_(False),
             qb + matrix_buckets(adj1))
    if use_cache:
        state = state + (cache,)
    bes_out = jax.lax.while_loop(bes_cond, bes_body, state)
    adj2, n_del, qb = bes_out[0], bes_out[2], bes_out[4]

    score = bdeu.graph_score_jax(data, arities, adj2, ess, max_q, r_max,
                                 counts_impl, data_axis_name=data_axis_name)
    if use_cache:
        return adj2, score, n_ins, n_del, qb, bes_out[5]
    return adj2, score, n_ins, n_del, qb


@lru_cache(maxsize=None)
def _sharded_ges_prog(d, ess, max_parents, max_q, r_max, counts_impl, tol,
                      incremental, child_chunk):
    """Compiled full-GES program over a d-device data-axis mesh: the whole
    ges_jit_body runs under shard_map with the (m, n) rows sharded
    P("data") and everything else (graphs, pid table, cache state)
    replicated, so every count build contracts m/d rows and psums.  All
    outputs are data-axis-replicated (psum'd scores, lockstep cache), hence
    the blanket ``P()`` out_spec.  Optional pid_table/cache arguments pass
    through as pytrees (None == empty pytree), so one cache entry serves
    all four present/absent combinations per static config."""
    mesh = _data_mesh(d)

    def body(data, arities, init_adj, allowed, add_limit, pid_table, cache):
        return ges_jit_body(data, arities, init_adj, allowed, add_limit,
                            ess, max_parents, max_q, r_max, counts_impl,
                            tol, incremental, child_chunk,
                            pid_table=pid_table, data_axis_name=DATA_AXIS,
                            cache=cache)

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(DATA_AXIS),) +
                 (jax.sharding.PartitionSpec(),) * 6,
        out_specs=jax.sharding.PartitionSpec(), check_vma=False))


def ges_jit(
    data: Array,
    arities: Array,
    init_adj: Array,
    allowed: Array,
    add_limit: Optional[int] = None,
    config: Optional[GESConfig] = None,
    r_max: Optional[int] = None,
    pid_table: Optional[Array] = None,
    cache: Optional[score_cache.FamilyScoreCache] = None,
    return_cache: bool = False,
    return_q_buckets: bool = False,
):
    """Fully-compiled GES. ``add_limit=None`` means unlimited (n^2 cap).

    ``pid_table``: optional (n, W) restricted candidate table — the compiled
    program then sweeps W-wide end-to-end (see ges_jit_body).  The table must
    cover ``allowed`` column-for-column (partition.pid_table_from_allowed
    builds it); candidates absent from the table are never scored.

    ``cache``: optional persistent family-score cache state carried across
    calls (auto-created when ``config.family_cache`` and omitted).  Pass
    ``return_cache=True`` to receive ``(adj, score, n_ins, n_del, cache')``
    so the warmed state can seed the next round; the cached trajectory is
    bitwise-identical to the uncached one (exact keys — see core/score_cache).
    ``return_q_buckets=True`` appends the ``ges.q_buckets`` histogram of
    column reductions per row bucket (see ges_jit_body) to what is returned.
    """
    config = config if config is not None else GESConfig()
    n = init_adj.shape[0]
    lim = jnp.int32(n * n if add_limit is None else add_limit)
    if r_max is None:
        r_max = int(np.asarray(arities).max())
    if cache is None and config.family_cache:
        width = int(pid_table.shape[1]) if pid_table is not None else n
        cache = score_cache.init(n, width, config.cache_capacity)
    if config.data_shards > 1:
        d = config.data_shards
        prog = _sharded_ges_prog(
            d, config.ess, config.max_parents, config.max_q, r_max,
            config.counts_impl, config.tol, config.incremental,
            config.child_chunk)
        out = prog(pad_data_rows(jnp.asarray(data), r_max, d),
                   jnp.asarray(arities), jnp.asarray(init_adj),
                   jnp.asarray(allowed), lim, pid_table, cache)
    else:
        out = _ges_jit_impl(
            data, arities, init_adj, allowed, lim, pid_table,
            config.ess, config.max_parents, config.max_q, r_max,
            config.counts_impl, config.tol, config.incremental,
            config.child_chunk, cache, jnp.int32(0))
    res = tuple(out[:4])
    if cache is not None and return_cache:
        res += (out[5],)
    if return_q_buckets:
        res += (out[4],)
    return res
