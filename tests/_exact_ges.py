"""Plain-numpy GES / cGES-ring oracle with exact (float64, libm lgamma)
local scores — the reference the compiled ring's trajectories are pinned to.

Semantics mirror ``repro.core.ges``'s GES loops: greedy FES over allowed,
acyclic, ``max_parents``- and ``max_q``-guarded insertions (optionally capped
at ``add_limit``), then greedy BES over allowed edges; ties go to the lowest
full-n flat index, and score-equivalent insertions (Pa_x == Pa_y) take the
direction min(x, y) -> max(x, y).  Quadratic in n per step: for small tests.
"""
from __future__ import annotations

import numpy as np

from repro.core import bdeu, fusion
from repro.core.dag import transitive_closure_np


def exact_ges(data, arities, init, allowed, add_limit=None, max_q=4096,
              max_parents=6, ess=10.0, tol=1e-9):
    n = data.shape[1]
    adj = np.asarray(init, dtype=np.int8).copy()
    allowed = np.asarray(allowed, dtype=bool).copy()
    np.fill_diagonal(allowed, False)

    def score(y, parents):
        return bdeu.local_score_np(data, arities, y, list(parents), ess)

    base = [score(y, np.flatnonzero(adj[:, y])) for y in range(n)]
    n_ins = 0
    while add_limit is None or n_ins < add_limit:
        reach = transitive_closure_np(adj.astype(bool))
        D = np.full((n, n), -np.inf)
        for y in range(n):
            pa = list(np.flatnonzero(adj[:, y]))
            q = int(np.prod(arities[pa])) if pa else 1
            if len(pa) >= max_parents:
                continue
            for x in range(n):
                if (allowed[x, y] and not adj[x, y] and not reach[y, x]
                        and q * arities[x] <= max_q):
                    D[x, y] = score(y, pa + [x]) - base[y]
        x, y = np.unravel_index(np.argmax(D), D.shape)
        if not np.isfinite(D[x, y]) or D[x, y] <= tol:
            break
        if (x > y and np.isfinite(D[y, x])
                and np.array_equal(adj[:, x], adj[:, y])):
            x, y = y, x
        adj[x, y] = 1
        base[y] = score(y, np.flatnonzero(adj[:, y]))
        n_ins += 1
    while True:
        D = np.full((n, n), -np.inf)
        for y in range(n):
            pa = np.flatnonzero(adj[:, y])
            for x in pa:
                if allowed[x, y]:
                    D[x, y] = score(y, pa[pa != x]) - base[y]
        x, y = np.unravel_index(np.argmax(D), D.shape)
        if not np.isfinite(D[x, y]) or D[x, y] <= tol:
            break
        adj[x, y] = 0
        base[y] = score(y, np.flatnonzero(adj[:, y]))
    return adj, bdeu.graph_score_np(data, arities, adj, ess)


def exact_ring(data, arities, edge_masks, add_limit=None, max_q=4096,
               max_rounds=16, tol=1e-9):
    """Algorithm 1's learning stage (core/ring._ring_body semantics): each
    round every member fuses its graph with its predecessor's and runs
    exact GES on its E_i; returns the graphs and scores of the last
    globally-improving round, and the rounds executed."""
    k, n, _ = edge_masks.shape
    graphs = [np.zeros((n, n), np.int8)] * k
    best_g, best_s = list(graphs), [-np.inf] * k
    best, go, rnd = -np.inf, True, 0
    while go and rnd < max_rounds:
        out = [exact_ges(data, arities,
                         fusion.fusion_edge_union(graphs[i],
                                                  graphs[(i - 1) % k]),
                         edge_masks[i], add_limit, max_q)
               for i in range(k)]
        graphs = [g for g, _ in out]
        scores = [s for _, s in out]
        rnd += 1
        go = max(scores) > best + tol
        if go:
            best_g, best_s = graphs, scores
        best = max(best, max(scores))
    return np.stack(best_g), np.array(best_s), rnd
