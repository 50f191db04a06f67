"""The plain reference: BDeu, GES's search space and stage-1 partitioning,
written from their definitions in numpy and scipy.

It imports nothing of the program and takes nothing the program made except
the answer under test.  Scores are float64 unless a caller asks for
``"bfloat16"``, which rounds every value the BDeu reduction forms (the
counts' lgamma arguments, each lgamma, each sum and difference) to bfloat16:
the control that a lower-precision scorer would give.

Definitions (arXiv:2409.13314 section 2 and 3; Heckerman et al. 1995):

* BDeu(y | Pa) = sum_j [lg(a_j) - lg(a_j + N_j)]
                 + sum_jk [lg(a_jk + N_jk) - lg(a_jk)],
  a_j = ess / q, a_jk = ess / (q r_y), q = prod of the parents' arities.
  Configurations never observed contribute 0.
* GES's search space: DAGs whose families have at most ``max_parents``
  parents and q <= ``max_q`` configurations.  An insertion x -> y is legal
  when x != y, x -> y is absent, y does not reach x, and both bounds hold
  after it.
* Stage 1: s(X_i, X_j) = [BDeu(i | j) - BDeu(i)] symmetrised (Eq. 4),
  average-linkage agglomeration to k clusters (Eq. 5), within-cluster edges
  to their cluster's subset, and each cross pair (x ascending, then y) to
  the currently smallest subset, both directions together.
* A ring member's step in round t > 1 (Algorithm 1, lines 9-10): fuse its
  own graph of round t-1 with its predecessor's (Puerta et al. 2021: a
  common order sigma built back to front, each time taking the node with
  the fewest out-edges to the remaining nodes summed over both graphs,
  lowest index first; each graph made sigma-consistent by covered-edge
  reversals, sinking each node into the remaining subgraph through its
  out-neighbour of least longest-path depth, lowest index first; the
  union), then GES restricted to its subset E_i, FES capped at the cGES-L
  limit (10/k) sqrt(n).  Round 1 starts from the empty graph.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np
from scipy.special import gammaln

def _threads() -> int:
    return max(1, min(8, os.cpu_count() or 1))


# ---------------------------------------------------------------------------
# Arithmetic in the requested precision
# ---------------------------------------------------------------------------

def _rnd(x, precision: str):
    x = np.asarray(x, dtype=np.float64)
    if precision == "float64":
        return x
    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def _sum(x, axis, precision: str):
    """Sum along ``axis``; in bfloat16 a pairwise tree that rounds each add."""
    if precision == "float64":
        return np.sum(x, axis=axis)
    x = np.moveaxis(np.asarray(x, dtype=np.float64), axis, -1)
    while x.shape[-1] > 1:
        if x.shape[-1] % 2:
            x = np.concatenate([x, np.zeros(x.shape[:-1] + (1,))], axis=-1)
        x = _rnd(x[..., 0::2] + x[..., 1::2], precision)
    return x[..., 0] if x.shape[-1] else np.zeros(x.shape[:-1])


def _bdeu(counts: np.ndarray, q, r, ess: float, precision: str):
    """BDeu of count tables ``counts[..., j, k]`` (last two axes: parent
    configuration, child value) with q configurations and r child values,
    q and r broadcasting against the leading axes."""
    q = np.asarray(q, dtype=np.float64)[..., None]
    r = np.asarray(r, dtype=np.float64)[..., None, None]
    a_j = _rnd(ess / q, precision)
    a_jk = _rnd(ess / (q[..., None] * r), precision)
    n_j = counts.sum(axis=-1)
    lg = lambda v: _rnd(gammaln(_rnd(v, precision)), precision)   # noqa: E731
    seen = n_j > 0
    t_j = np.where(seen, _rnd(lg(a_j) - lg(a_j + n_j), precision), 0.0)
    t_jk = np.where(counts > 0, _rnd(lg(a_jk + counts) - lg(a_jk), precision),
                    0.0)
    per_j = _rnd(t_j + _sum(t_jk, -1, precision), precision)
    return _sum(per_j, -1, precision)


def _codes(data, arities, parents):
    """Radix code of each instance's configuration of ``parents``, and q."""
    cfg = np.zeros(data.shape[0], dtype=np.int64)
    q = 1
    for p in parents:
        cfg = cfg * int(arities[p]) + data[:, p]
        q *= int(arities[p])
    return cfg, q


def family_score(data, arities, y, parents, ess, precision="float64"):
    parents = sorted(int(p) for p in parents)
    r = int(arities[y])
    cfg, q = _codes(data, arities, parents)
    uniq, inv = np.unique(cfg, return_inverse=True)
    counts = np.bincount(inv * r + data[:, y],
                         minlength=uniq.size * r).reshape(uniq.size, r)
    return float(_bdeu(counts, q, r, ess, precision))


def family_scores(data, arities, adj, ess, precision="float64"):
    adj = np.asarray(adj, dtype=bool)
    return np.array([family_score(data, arities, y, np.flatnonzero(adj[:, y]),
                                  ess, precision)
                     for y in range(adj.shape[0])])


def graph_score(data, arities, adj, ess, precision="float64") -> float:
    return float(_sum(family_scores(data, arities, adj, ess, precision), 0,
                      precision))


# ---------------------------------------------------------------------------
# GES's search space at a graph
# ---------------------------------------------------------------------------

def reach(adj) -> np.ndarray:
    """reach[a, b]: a directed path a -> ... -> b exists."""
    r = np.array(adj, dtype=bool)
    while True:
        nxt = r | ((r.astype(np.float32) @ r.astype(np.float32)) > 0)
        if np.array_equal(nxt, r):
            return r
        r = nxt


def structure_faults(adj, arities, max_parents, max_q) -> int:
    """Cycles (1 if any) plus families over the parent or q bound."""
    adj = np.asarray(adj, dtype=bool)
    cyclic = int(np.diag(reach(adj)).any())
    over = 0
    for y in range(adj.shape[0]):
        pa = np.flatnonzero(adj[:, y])
        q = int(np.prod(arities[pa])) if pa.size else 1
        over += int(pa.size > max_parents or q > max_q)
    return cyclic + over


def family_q(adj, arities) -> np.ndarray:
    """(n,) number of parent configurations of each family."""
    adj = np.asarray(adj, dtype=bool)
    return np.array([int(np.prod(arities[adj[:, y]])) for y in
                     range(adj.shape[0])], dtype=np.int64)


def legal_inserts(adj, arities, max_parents, max_q, reach_=None):
    """(n, n) bool: inserting x -> y (entry [x, y]) stays in the space."""
    adj = np.asarray(adj, dtype=bool)
    n = adj.shape[0]
    rch = reach(adj) if reach_ is None else reach_
    pa_count = adj.sum(axis=0)
    q_ok = (family_q(adj, arities)[None, :]
            * arities.astype(np.int64)[:, None] <= max_q)
    return (~adj & ~rch.T & ~np.eye(n, dtype=bool)
            & (pa_count < max_parents)[None, :] & q_ok)


# Families with at most this many cells are counted by a product of one-hot
# codes, larger ones by one bincount over the instances.
_DENSE_FAMILY = 96
_ONEHOT: dict = {}


def _onehot(data, r_max) -> np.ndarray:
    """(m, n * r_max) float32 one-hot codes of ``data``, kept for the last
    data array asked for."""
    key = (id(data), data.shape, r_max)
    if _ONEHOT.get("key") != key:
        m, n = data.shape
        out = np.zeros((m, n * r_max), dtype=np.float32)
        out[np.arange(m)[:, None], np.arange(n)[None, :] * r_max + data] = 1.0
        _ONEHOT.update(key=key, data=data, onehot=out)
    return _ONEHOT["onehot"]


def insert_column(data, arities, adj, y, ess, precision="float64",
                  cands=None):
    """(n,) gain of inserting each x -> y into ``adj`` (legality aside):
    every candidate family's table from one count over the instances.  With
    ``cands`` (a bool (n,) mask) only those x are scored; the rest read
    -inf."""
    m, n = data.shape
    r_max = int(arities.max())
    xs = (np.arange(n) if cands is None
          else np.flatnonzero(np.asarray(cands, dtype=bool)))
    out = np.full(n, -np.inf)
    if xs.size == 0:
        return out
    parents = np.flatnonzero(np.asarray(adj)[:, y])
    cfg, q = _codes(data, arities, parents)
    r_y = int(arities[y])
    base = (cfg * r_y + data[:, y]).astype(np.int64)
    c = xs.size
    if q * r_y <= _DENSE_FAMILY:
        # [x value a of candidate x, family cell (j0, b)]: a product of
        # one-hot codes, exact in float32 below 2**24 instances
        fam = np.zeros((q * r_y, m), dtype=np.float32)
        fam[base, np.arange(m)] = 1.0
        counts = (fam @ _onehot(data, r_max)).reshape(q, r_y, n, r_max)
        counts = counts[:, :, xs].transpose(2, 3, 0, 1).astype(np.int64)
    else:
        slot = np.arange(c, dtype=np.int64) * r_max
        key = (slot[None, :] + data[:, xs]) * (q * r_y) + base[:, None]
        counts = np.bincount(key.ravel(), minlength=c * r_max * q * r_y)
    counts = counts.reshape(c, r_max * q, r_y)        # [x, (a, j0), b]
    new = _bdeu(counts, q * arities[xs].astype(np.float64), r_y, ess,
                precision)
    old = family_score(data, arities, y, parents, ess, precision)
    out[xs] = _rnd(new - old, precision)
    return out


def insert_matrix(data, arities, adj, ess, precision="float64",
                  allowed=None):
    """(n, n) gains [x, y] of inserting x -> y (legality aside); -inf
    outside ``allowed`` when it is given."""
    n = data.shape[1]
    cols = range(n)
    out = np.empty((n, n))
    with ThreadPoolExecutor(_threads()) as ex:
        for y, col in zip(cols, ex.map(
                lambda y: insert_column(
                    data, arities, adj, y, ess, precision,
                    None if allowed is None else allowed[:, y]), cols)):
            out[:, y] = col
    return out


def delete_column(data, arities, adj, y, ess, precision="float64"):
    """(n,) gain of deleting each parent x -> y (-inf where no edge)."""
    n = data.shape[1]
    parents = np.flatnonzero(np.asarray(adj)[:, y])
    out = np.full(n, -np.inf)
    if parents.size == 0:
        return out
    old = family_score(data, arities, y, parents, ess, precision)
    for x in parents:
        rest = parents[parents != x]
        out[x] = _rnd(family_score(data, arities, y, rest, ess, precision)
                      - old, precision)
    return out


def delete_matrix(data, arities, adj, ess, precision="float64"):
    n = data.shape[1]
    return np.stack([delete_column(data, arities, adj, y, ess, precision)
                     for y in range(n)], axis=1)


# ---------------------------------------------------------------------------
# Stage 1: edge partitioning
# ---------------------------------------------------------------------------

def similarity(data, arities, ess) -> np.ndarray:
    """Eq. 4 for every pair, from exact pairwise counts."""
    m, n = data.shape
    r_max = int(arities.max())
    onehot = np.zeros((m, n * r_max), dtype=np.float32)
    onehot[np.arange(m)[:, None],
           np.arange(n)[None, :] * r_max + data] = 1.0
    pair = (onehot.T @ onehot).astype(np.float64)      # exact below 2**24
    pair = pair.reshape(n, r_max, n, r_max)
    r = arities.astype(np.float64)
    # child i, parent j: table [j value, i value] = pair[j, :, i, :]
    tables = pair.transpose(2, 0, 1, 3)                # [i, j, a, b]
    with_parent = _bdeu(tables, r[None, :], r[:, None], ess, "float64")
    alone = np.array([_bdeu(pair[i, :, i, :].diagonal()[None, :], 1.0,
                            r[i], ess, "float64") for i in range(n)])
    d = with_parent - alone[:, None]
    s = 0.5 * (d + d.T)
    np.fill_diagonal(s, 0.0)
    return s


def clusters(sim: np.ndarray, k: int) -> list:
    """Average-linkage agglomeration down to k clusters; ties go to the
    lexicographically first pair.  Clusters are listed by their smallest
    member."""
    n = sim.shape[0]
    if k >= n:
        return [[i] for i in range(n)]
    total = sim.astype(np.float64).copy()    # total[a, b]: sum of pair sims
    np.fill_diagonal(total, 0.0)
    size = np.ones(n, dtype=np.int64)
    alive = np.ones(n, dtype=bool)
    members = [[i] for i in range(n)]
    lower = np.tril(np.ones((n, n), dtype=bool))
    for _ in range(n - k):
        link = total / np.outer(size, size).astype(np.float64)
        link[~alive, :] = -np.inf
        link[:, ~alive] = -np.inf
        link[lower] = -np.inf
        a, b = np.unravel_index(int(np.argmax(link)), link.shape)
        members[a] += members[b]
        members[b] = []
        total[a, :] += total[b, :]
        total[:, a] += total[:, b]
        total[a, a] = 0.0
        size[a] += size[b]
        alive[b] = False
        total[b, :] = 0.0
        total[:, b] = 0.0
    return [sorted(c) for c in members if c]


def edge_subsets(groups: list, n: int) -> np.ndarray:
    k = len(groups)
    masks = np.zeros((k, n, n), dtype=bool)
    owner = np.empty(n, dtype=np.int64)
    for c, g in enumerate(groups):
        owner[g] = c
        masks[c][np.ix_(g, g)] = True
        np.fill_diagonal(masks[c], False)
    sizes = [int(s) for s in masks.sum(axis=(1, 2))]
    for x in range(n):
        for y in range(x + 1, n):
            if owner[x] == owner[y]:
                continue
            t = sizes.index(min(sizes))
            masks[t, x, y] = masks[t, y, x] = True
            sizes[t] += 2
    return masks


def partition(data, arities, k, ess) -> np.ndarray:
    """(k, n, n) bool edge subsets E_1..E_k."""
    return edge_subsets(clusters(similarity(data, arities, ess), k),
                        data.shape[1])


# ---------------------------------------------------------------------------
# A plain GES (FES then BES)
# ---------------------------------------------------------------------------

def add_limit(n: int, k: int) -> int:
    """cGES-L's cap on a member's insertions per round: (10 / k) sqrt(n),
    rounded, at least 1."""
    return max(1, int(round((10.0 / k) * np.sqrt(n))))


def ges(data, arities, ess, max_parents, max_q, precision="float64",
        start=None, allowed=None, limit=None):
    """Greedy FES then BES from ``start`` (the empty graph by default).
    Returns (adj, score) with the score in ``precision``.

    With ``allowed`` (a symmetric (n, n) bool mask) both phases only insert
    and delete edges inside it, and ``limit`` caps the insertions FES makes:
    a cGES-L ring member's step.  Among equal gains the first [x, y] in
    row-major order wins; a score-equivalent insertion (equal parent sets)
    is made from the lower index to the higher."""
    n = data.shape[1]
    adj = (np.zeros((n, n), dtype=bool) if start is None
           else np.asarray(start, dtype=bool).copy())
    ok = (np.ones((n, n), dtype=bool) if allowed is None
          else np.asarray(allowed, dtype=bool).copy())
    np.fill_diagonal(ok, False)
    rch = reach(adj)
    gains = insert_matrix(data, arities, adj, ess, precision,
                          None if allowed is None else ok)
    n_ins = 0
    while limit is None or n_ins < limit:
        legal = ok & legal_inserts(adj, arities, max_parents, max_q, rch)
        masked = np.where(legal, gains, -np.inf)
        x, y = np.unravel_index(int(np.argmax(masked)), masked.shape)
        if not masked[x, y] > 0:
            break
        if x > y and legal[y, x] and np.array_equal(adj[:, x], adj[:, y]):
            x, y = y, x
        adj[x, y] = True
        n_ins += 1
        into_x = rch[:, x].copy()
        into_x[x] = True
        from_y = rch[y, :].copy()
        from_y[y] = True
        rch |= into_x[:, None] & from_y[None, :]
        gains[:, y] = insert_column(data, arities, adj, y, ess, precision,
                                    None if allowed is None else ok[:, y])
    gains = np.where(ok, delete_matrix(data, arities, adj, ess, precision),
                     -np.inf)
    while True:
        x, y = np.unravel_index(int(np.argmax(gains)), gains.shape)
        if not gains[x, y] > 0:
            break
        adj[x, y] = False
        gains[:, y] = np.where(ok[:, y], delete_column(
            data, arities, adj, y, ess, precision), -np.inf)
    return adj.astype(np.int8), graph_score(data, arities, adj, ess,
                                            precision)


# ---------------------------------------------------------------------------
# Fusion of a member's graph with its predecessor's
# ---------------------------------------------------------------------------

def depth(adj, in_s) -> np.ndarray:
    """Longest-path layer of each node of the subgraph induced on ``in_s``
    (0 for its sources); -1 outside it."""
    sub = np.asarray(adj, dtype=bool) & in_s[:, None] & in_s[None, :]
    d = np.where(in_s, 0, -1)
    for _ in range(adj.shape[0] + 1):
        nxt = np.where(in_s, np.maximum(
            np.where(sub, d[:, None], -1).max(axis=0) + 1, 0), -1)
        if np.array_equal(nxt, d):
            return d
        d = nxt
    raise ValueError("graph has a cycle")


def fusion_order(adjs) -> np.ndarray:
    """sigma, built from the back: each time the remaining node with the
    fewest out-edges to remaining nodes, summed over ``adjs``."""
    total = sum(np.asarray(a, dtype=np.int64) for a in adjs)
    n = total.shape[0]
    remaining = np.ones(n, dtype=bool)
    order = np.empty(n, dtype=np.int64)
    for pos in range(n - 1, -1, -1):
        cost = np.where(remaining, (total * remaining[None, :]).sum(axis=1),
                        np.iinfo(np.int64).max)
        v = int(np.argmin(cost))
        order[pos] = v
        remaining[v] = False
    return order


def sigma_consistent(adj, order) -> np.ndarray:
    """``adj`` made consistent with ``order`` by covered-edge reversals."""
    adj = np.asarray(adj, dtype=bool).copy()
    n = adj.shape[0]
    in_s = np.ones(n, dtype=bool)
    for v in order[::-1]:
        while True:
            out = np.flatnonzero(adj[v] & in_s)
            if out.size == 0:
                break
            w = int(out[np.argmin(depth(adj, in_s)[out])])
            pa_v, pa_w = adj[:, v].copy(), adj[:, w].copy()
            to_w = pa_v & ~pa_w
            to_v = pa_w & ~pa_v
            to_w[[v, w]] = False
            to_v[[v, w]] = False
            adj[:, w] |= to_w
            adj[:, v] |= to_v
            adj[v, w], adj[w, v] = False, True
        in_s[v] = False
    return adj


def fuse(own, pred) -> np.ndarray:
    """A member's start: its own graph fused with its predecessor's (either
    one alone when the other is empty)."""
    own, pred = np.asarray(own, dtype=bool), np.asarray(pred, dtype=bool)
    if not own.any():
        return pred.copy()
    if not pred.any():
        return own.copy()
    order = fusion_order([own, pred])
    return sigma_consistent(own, order) | sigma_consistent(pred, order)
