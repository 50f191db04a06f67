"""Edge partitioning (paper §3 stage 1).

Score-guided agglomerative clustering of *variables* using the BDeu-delta
similarity s(X_i, X_j) (Eq. 4), merged with the average-pairwise linkage of
Eq. 5 (the paper labels it complete-link but writes the average formula — we
implement the formula).  The k variable clusters induce k disjoint edge
subsets: within-cluster edges go to their cluster; cross-cluster edges are
assigned to the currently smallest subset (load balancing, as in the paper).
"""
from __future__ import annotations

from typing import List

import numpy as np
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from . import bdeu


def variable_clusters(similarity: np.ndarray, k: int) -> List[List[int]]:
    """Agglomerative clustering with Eq.-5 average linkage down to k clusters."""
    n = similarity.shape[0]
    if k >= n:
        return [[i] for i in range(n)]
    clusters: List[List[int]] = [[i] for i in range(n)]
    # Pairwise *sum* of similarities between clusters; Eq. 5 divides by
    # |Cr||Cl| when comparing.
    sims = similarity.astype(np.float64).copy()
    np.fill_diagonal(sims, 0.0)
    sum_s = sims.copy()                     # sum_s[a, b] = sum of pair sims
    sizes = np.ones(n, dtype=np.int64)
    alive = np.ones(n, dtype=bool)

    while alive.sum() > k:
        denom = np.outer(sizes, sizes).astype(np.float64)
        with np.errstate(invalid="ignore"):
            link = sum_s / denom
        link[~alive, :] = -np.inf
        link[:, ~alive] = -np.inf
        np.fill_diagonal(link, -np.inf)
        a, b = np.unravel_index(np.argmax(link), link.shape)
        if a > b:
            a, b = b, a
        # merge b into a
        clusters[a] = clusters[a] + clusters[b]
        clusters[b] = []
        sum_s[a, :] += sum_s[b, :]
        sum_s[:, a] += sum_s[:, b]
        sum_s[a, a] = 0.0
        sizes[a] += sizes[b]
        alive[b] = False
        sum_s[b, :] = 0.0
        sum_s[:, b] = 0.0

    return [c for c in clusters if c]


def edge_subsets(clusters: List[List[int]], n: int) -> np.ndarray:
    """Return (k, n, n) boolean masks E_1..E_k — disjoint, covering all
    off-diagonal ordered pairs.

    Within-cluster edges -> that cluster's subset.  Cross-cluster edges are
    assigned (both directions together, X->Y and Y->X) to the subset that is
    currently smallest, per the paper's balancing rule.

    The greedy smallest-subset assignment is fully vectorized: walking the
    cross pairs in deterministic (x asc, y asc) order and giving each to the
    currently-smallest subset (+2 edges, ties -> lowest index) is exactly the
    k-way merge of k sorted streams — subset i's c-th grab happens at size
    ``sizes[i] + 2c`` — so sorting all (size, index) tokens lexicographically
    and keeping the first P reproduces the sequential loop's target sequence
    token-for-token (mask-identity regression-tested).  The old O(n^2)
    Python loop was ~500k iterations at the paper's n = 1000 and dominated
    stage 1.
    """
    k = len(clusters)
    masks = np.zeros((k, n, n), dtype=bool)
    cluster_of = np.empty(n, dtype=np.int64)
    for ci, members in enumerate(clusters):
        idx = np.asarray(members, dtype=np.int64)
        cluster_of[idx] = ci
        if idx.size:
            masks[ci][np.ix_(idx, idx)] = True
            np.fill_diagonal(masks[ci], False)
    sizes = masks.sum(axis=(1, 2))

    # deterministic order over cross pairs: x ascending, then y ascending
    xs, ys = np.triu_indices(n, 1)
    cross = cluster_of[xs] != cluster_of[ys] if n else np.zeros(0, bool)
    xs, ys = xs[cross], ys[cross]
    p = xs.size
    if p:
        c = np.arange(p, dtype=np.int64)
        vals = sizes[:, None].astype(np.int64) + 2 * c[None, :]     # (k, p)
        subset = np.broadcast_to(np.arange(k)[:, None], (k, p))
        order = np.lexsort((subset.ravel(), vals.ravel()))[:p]
        tgt = order // p                       # token row = its subset index
        masks[tgt, xs, ys] = True
        masks[tgt, ys, xs] = True
    return masks


def pid_table_from_allowed(allowed: np.ndarray,
                           width: int | None = None) -> np.ndarray:
    """Static (n, W) candidate-parent table for one allowed-edge mask.

    Row y lists the candidate parents x with ``allowed[x, y]`` (ascending),
    padded to the static width W with ``y`` itself — a self-loop, which every
    sweep masks to -inf, so padding slots can never be selected.  W defaults
    to the max column occupancy of ``allowed`` (at least 1); it may be forced
    wider with ``width`` (the ring pads all k processes to one shared W so
    the shard_map program has a single static shape).

    This is the device-side form of the paper's restricted edge sets E_i:
    a compiled sweep over the table pays W = |E_i| per column, not n.

    Degenerate shapes are well-defined rather than errors: n == 0 yields a
    (0, 0) table (nothing to sweep), n == 1 and all-empty masks yield
    all-self-pad tables (every slot invalid by convention, so sweeps return
    all--inf columns) — the shapes an empty E_i or a trivial partition hands
    the ring.
    """
    allowed = np.asarray(allowed, dtype=bool).copy()
    n = allowed.shape[0]
    if n:
        np.fill_diagonal(allowed, False)
    occ = int(allowed.sum(axis=0).max()) if n else 0
    W = (max(1, occ) if n else 0) if width is None else int(width)
    if W < occ:
        raise ValueError(f"width {W} < max column occupancy {occ}")
    if W > n:
        raise ValueError(f"width {W} exceeds n = {n}")
    table = np.empty((n, W), dtype=np.int32)
    for y in range(n):
        ids = np.flatnonzero(allowed[:, y])
        table[y, :ids.size] = ids
        table[y, ids.size:] = y              # self-pad (invalid by convention)
    return table


def pid_tables(edge_masks: np.ndarray, width: int | None = None) -> np.ndarray:
    """(k, n, W) per-process candidate tables from (k, n, n) edge masks E_i.

    All processes share one static W (the max column occupancy over the whole
    partition, or ``width``) so the tables can ride a shard_map axis.

    Degenerate inputs (n in {0, 1}, all-empty E_i) produce well-defined
    all-self-pad / zero-width tables instead of raising — see
    :func:`pid_table_from_allowed`.
    """
    k, n, _ = edge_masks.shape
    masks = np.asarray(edge_masks, dtype=bool)
    occ = 0
    for i in range(k):
        off = masks[i].copy()
        if n:
            np.fill_diagonal(off, False)
            occ = max(occ, int(off.sum(axis=0).max()))
    W = (max(1, occ) if n else 0) if width is None else int(width)
    return np.stack([pid_table_from_allowed(masks[i], width=W)
                     for i in range(k)]) if k else np.zeros((0, n, W),
                                                            dtype=np.int32)


def remerge_failed(edge_masks: np.ndarray, failed: int) -> np.ndarray:
    """Elastic ring repair: fold a failed member's edge subset into its ring
    predecessor.

    E_1..E_k are a disjoint cover of all candidate edges, so re-merging
    preserves the cover exactly — the ring shrinks from k to k-1 processes
    and the learning stage continues with no loss of search space.  (cGES's
    correctness only needs the union of subsets to equal E; the elastic-ring
    behaviour is exercised by tests/test_fault_tolerance.py.)
    """
    k = edge_masks.shape[0]
    pred = (failed - 1) % k
    out = np.delete(edge_masks, failed, axis=0).copy()
    new_pred = pred if pred < failed else pred - 1
    out[new_pred] |= edge_masks[failed]
    return out


def partition_edges(
    data: np.ndarray,
    arities: np.ndarray,
    k: int,
    ess: float = 10.0,
    engine: str = "fast",
) -> np.ndarray:
    """Full stage-1 pipeline: similarity -> clusters -> (k, n, n) edge masks.

    engine="fast" (default) computes ALL n^2 pairwise tables from one
    contingency matmul (bdeu.pairwise_similarity_fast) — same values as the
    per-pair oracles, ~1000x fewer dispatches (see EXPERIMENTS §Perf it.0).
    """
    n = data.shape[1]
    with TraceAnnotation("partition.similarity"):
        if engine == "host":
            sims = bdeu.pairwise_similarity_np(data, arities, ess)
        elif engine == "fast":
            sims = bdeu.pairwise_similarity_fast(data, arities, ess)
        elif engine == "jax":
            r_max = int(arities.max())
            sims = np.asarray(
                bdeu.pairwise_similarity_jax(
                    jnp.asarray(data.astype(np.int32)),
                    jnp.asarray(arities.astype(np.int32)),
                    ess, r_max,
                )
            )
        else:
            raise ValueError(
                f"partition_edges: unknown engine {engine!r} "
                f"(valid: 'host', 'fast', 'jax')")
    with TraceAnnotation("partition.clusters"):
        clusters = variable_clusters(sims, k)
        return edge_subsets(clusters, n)
