"""Datasets for the cells, made from the configuration and ``--seed``.

A configuration fixes one network and one base sample of it, the way the
paper fixes the pigs or link network and samples its instances: the
family-matched generator below draws the network from ``network_seed`` and
``m`` instances from ``data_seed``.  ``--seed`` then draws a relabelling of
the variables and an order of the instances.  Every seed so learns the same
problem in another order: the same sizes, the same W of the edge partition,
the same compiled programs, and a DAG that is the same up to the relabelling.

The generator is a copy of the family-matched sampler the program ships
(random DAG under a random topological order, Dirichlet CPTs, Gumbel-max
ancestral sampling), kept here so that no change to the program can change
the benchmark's data.  It makes the same draws as that sampler for the same
seeds.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Problem:
    data: np.ndarray        # (m, n) int32 instance codes
    arities: np.ndarray     # (n,) int64


def random_dag(rng: np.random.Generator, n: int, n_edges: int,
               max_parents: int) -> np.ndarray:
    """About ``n_edges`` arcs under a random topological order, at most
    ``max_parents`` parents per node."""
    order = rng.permutation(n)
    adj = np.zeros((n, n), dtype=bool)
    pairs = [(i, j) for j in range(1, n) for i in range(j)]
    rng.shuffle(pairs)
    added = 0
    indeg = np.zeros(n, dtype=np.int64)
    for i, j in pairs:
        if added >= n_edges:
            break
        x, y = int(order[i]), int(order[j])
        if indeg[y] >= max_parents:
            continue
        adj[x, y] = True
        indeg[y] += 1
        added += 1
    return adj


def topological_order(adj: np.ndarray) -> list:
    """Kahn's algorithm, smallest ready node first."""
    adj = adj.astype(bool).copy()
    indeg = adj.sum(axis=0)
    ready = sorted(np.flatnonzero(indeg == 0).tolist())
    order = []
    while ready:
        v = ready.pop(0)
        order.append(v)
        for w in np.flatnonzero(adj[v]):
            adj[v, w] = False
            indeg[w] -= 1
            if indeg[w] == 0:
                ready.append(int(w))
        ready.sort()
    if len(order) != adj.shape[0]:
        raise ValueError("graph has a cycle")
    return order


def network(cfg: dict):
    """(adj, arities, cpts, parent lists) of the configuration's network."""
    rng = np.random.default_rng(cfg["network_seed"])
    n = cfg["n"]
    adj = random_dag(rng, n, cfg["n_edges"], cfg["max_parents_true"])
    arities = rng.choice(np.asarray(cfg["arity_choices"]),
                         p=cfg["arity_probs"], size=n).astype(np.int64)
    cpts, plists = [], []
    for i in range(n):
        parents = np.flatnonzero(adj[:, i])
        q = int(np.prod(arities[parents])) if parents.size else 1
        cpts.append(rng.dirichlet(
            np.full(int(arities[i]), cfg["concentration"]), size=q))
        plists.append(parents)
    return adj, arities, cpts, plists


def sample(adj, arities, cpts, plists, m: int,
           rng: np.random.Generator) -> np.ndarray:
    """Ancestral sampling, one Gumbel-max draw per instance and node."""
    n = adj.shape[0]
    data = np.zeros((m, n), dtype=np.int32)
    rng.gumbel(size=(m, int(arities.max())))   # keeps the sampler's draws
    for v in topological_order(adj):
        cfg = np.zeros(m, dtype=np.int64)
        for p in plists[v]:
            cfg = cfg * int(arities[p]) + data[:, p]
        probs = cpts[v][cfg]
        g = rng.gumbel(size=probs.shape)
        data[:, v] = np.argmax(np.log(probs + 1e-300) + g, axis=1)
    return data


def base_problem(cfg: dict) -> Problem:
    """The configuration's network and its base sample, before relabelling."""
    adj, arities, cpts, plists = network(cfg)
    data = sample(adj, arities, cpts, plists, cfg["m"],
                  np.random.default_rng(cfg["data_seed"]))
    return Problem(data=data, arities=arities)


def problem(cfg: dict, seed: int) -> Problem:
    """The base problem with its variables relabelled and its instances
    reordered by ``seed`` (any whole number >= 0)."""
    base = base_problem(cfg)
    rng = np.random.default_rng(int(seed))
    var = rng.permutation(base.data.shape[1])
    rows = rng.permutation(base.data.shape[0])
    return Problem(data=np.ascontiguousarray(base.data[rows][:, var]),
                   arities=base.arities[var])
