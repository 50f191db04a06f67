"""cGES — Circular (ring-distributed) GES.  Paper Algorithm 1.

Stages:
  1. Edge partitioning (partition.partition_edges) — once, up-front.
  2. Ring learning: k processes; per round, process i fuses its model with its
     ring predecessor's model (both from the previous round — one-hop
     information flow per round, exactly Figure 1) and runs GES restricted to
     its edge subset E_i, optionally capped at (10/k)*sqrt(n) insertions
     (cGES-L).
  3. Convergence: stop when no process improves on the best BDeu seen so far.
  4. Fine-tuning: one unrestricted GES (FES+BES) from the winner — this pass
     is what carries GES's theoretical guarantees over to cGES.

Engines:
  * engine="host": processes run as host tasks whose scoring sweeps are
    jit-batched (the faithful paper path; on a multi-device mesh the k tasks
    are dispatched concurrently by the ring executor in core/ring.py).
  * engine="jax": each process's GES is the fully-compiled ges_jit program —
    the building block the shard_map ring uses on device meshes.
  * engine="async": the asynchronous double-buffered ring
    (``core/ring_async.py``): k members run concurrently (threads here; the
    multi-process launcher is ``launch/ring_async_run.py``), each sweeping
    with ges_jit, exchanging BNs over sockets the moment a sweep finishes,
    with a circulating convergence token instead of a per-round barrier.
    Healthy runs follow the lockstep trajectory exactly; the engine also
    survives member death mid-run (elastic re-partition).

Both engines rescore exclusively through the unified sweep engine
(``core/sweeps.sweep``) and honour ``GESConfig.counts_impl``; with a fused
impl ("fused" / "fused_pallas") every column a ring process scores is fused:
insert columns are ONE joint contraction over the candidates
(bdeu.fused_insert_scores), and delete columns are ONE family-table build
marginalized per parent slot (bdeu.fused_delete_scores) — instead of one
table build per candidate in either phase.  BOTH engines sweep W-wide: the
host engine gathers each column down to its ``pids`` subset before scoring,
and ``engine="jax"`` passes each process's static (n, W) pid_table
(partition.pid_tables) into the compiled ges_jit while_loop, so the
fixed-shape program's per-round cost also tracks W = |E_i|, not n — the
constant factor that is decisive for the paper's n ~ 1000 workloads.  The
unrestricted fine-tuning pass stays full-n by construction (E = all edges).

Fusion goes through the unified layer in ``core/fusion.py``:
``fusion_engine`` picks the host (numpy) or traceable (jit) implementation
of the sigma-consistent edge union — adjacency-for-adjacency identical, so
the knob is purely a performance choice; ``None`` defaults from the
``REPRO_FUSION_ENGINE`` env var (mirroring ``REPRO_COUNTS_IMPL``) and
unknown values fail loudly up-front.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional

import numpy as np
import jax.numpy as jnp

from . import bdeu, fusion, partition
from .ges import (DeviceFamilyCache, GESConfig, GESResult, ScoreCache,
                  ges_host, ges_jit)


@dataclasses.dataclass
class CGESResult:
    adj: np.ndarray
    score: float
    rounds: int
    n_score_evals: int
    wall_time_s: float
    ring_scores: List[float]          # best score per round (trace)
    edge_masks: np.ndarray            # (k, n, n) partition actually used
    # wall time a k-worker deployment would see: ring rounds cost
    # max-over-processes (they run concurrently), partition+fine-tune serial.
    # (this container is 1-core, so the k processes run serially here; the
    # paper's Table 2c numbers are 8-thread wall times.)
    parallel_wall_s: float = 0.0
    # hits/misses/hit_rate of the persistent family-score cache, when
    # config.family_cache was on (host engine: the shared DeviceFamilyCache;
    # jax engine: summed per-member cache counters); None otherwise.
    family_cache_stats: Optional[dict] = None
    # lockstep engines: every member's graph and score in the last improving
    # round — the (graphs, scores) the compiled ring returns; the fine-tune
    # starts from the best of them.  None for engine="async".
    ring_graphs: Optional[np.ndarray] = None
    ring_graph_scores: Optional[np.ndarray] = None


def edge_add_limit(n: int, k: int) -> int:
    """cGES-L limit: (10 / k) * sqrt(n), at least 1."""
    return max(1, int(round((10.0 / k) * math.sqrt(n))))


def cges(
    data: np.ndarray,
    arities: np.ndarray,
    k: int = 4,
    limit: bool = True,
    config: Optional[GESConfig] = None,
    engine: str = "host",
    max_rounds: int = 50,
    edge_masks: Optional[np.ndarray] = None,
    seed_partition_ess: Optional[float] = None,
    fusion_engine: Optional[str] = None,
) -> CGESResult:
    t0 = time.perf_counter()
    m, n = data.shape
    k = int(k)
    if engine not in ("host", "jax", "async"):
        # Validate up front: an unknown engine used to silently run the host
        # path (the pre-PR 3 counts_impl fallthrough bug, lint rule R004).
        raise ValueError(
            f"cges: unknown engine {engine!r} "
            f"(valid: 'host', 'jax', 'async')")
    # built per call, not bound at import — honours REPRO_COUNTS_IMPL set
    # after ``import repro`` (see GESConfig.counts_impl)
    config = config if config is not None else GESConfig()
    # Resolve up-front so a typo'd engine (arg or REPRO_FUSION_ENGINE) fails
    # loudly before any learning work starts.
    fusion_engine = fusion.resolve_fusion_engine(fusion_engine)

    # ---- Stage 1: edge partitioning --------------------------------------
    if edge_masks is None:
        edge_masks = partition.partition_edges(
            data, arities, k,
            ess=(seed_partition_ess or config.ess),
            engine="fast",
        )
    add_limit = edge_add_limit(n, k) if limit else None
    parallel_wall = time.perf_counter() - t0          # stage 1 is serial

    graphs = [np.zeros((n, n), dtype=np.int8) for _ in range(k)]
    best_score = -np.inf
    best_adj = np.zeros((n, n), dtype=np.int8)
    best_graphs = best_graph_scores = None
    evals = 0
    ring_scores: List[float] = []
    # the paper's shared 'concurrent safe data structure': one score cache
    # shared by every ring process across every round
    cache = ScoreCache()
    # Persistent device-resident family-score caches (config.family_cache):
    # the host engine shares ONE DeviceFamilyCache handle across all k
    # processes, every round AND the fine-tune (full-n scattered columns,
    # scope-worded); the jax engine keeps one per-process cache pytree whose
    # warmed state is fed back into the next round's ges_jit call.
    dev_cache = (DeviceFamilyCache(n, config.cache_capacity)
                 if (config.family_cache and engine == "host") else None)
    jax_caches: List = [None] * k

    data_j = jnp.asarray(data.astype(np.int32))
    ar_j = jnp.asarray(arities.astype(np.int32))
    r_max = int(arities.max())
    # Static per-process E_i candidate tables (one shared W so all k
    # processes reuse ONE compiled ges_jit program): the compiled engine
    # sweeps W-wide end-to-end, mirroring the host engine's pids gather.
    pid_j = (jnp.asarray(partition.pid_tables(edge_masks))
             if engine == "jax" else None)

    # ---- Stage 2: ring learning ------------------------------------------
    if engine == "async":
        # concurrent members + circulating convergence token replace the
        # lockstep round loop below; healthy trajectories are identical
        from . import ring_async
        ring = ring_async.run_ring_async_threads(
            data, arities, edge_masks, config=config,
            add_limit=add_limit, max_rounds=max_rounds)
        rounds = int(ring["rounds"])
        ring_scores = [float(s) for s in ring["ring_scores"]]
        best_adj = np.asarray(ring["best_adj"], dtype=np.int8)
        best_score = float(ring["best_score"])
        evals += int(ring["n_score_evals"])
        # a real k-process deployment's ring wall time is the slowest
        # member's own busy+blocked span, not this 1-core serialization
        parallel_wall += max(
            sum(float(np.sum(results_i["timings"][ph]))
                for ph in ("wait_us", "fuse_us", "sweep_us"))
            for results_i in (ring["members"][i] for i in ring["survivors"])
        ) / 1e6
        return _finish_cges(
            data, arities, data_j, ar_j, r_max, best_adj,
            config, engine, cache, dev_cache, jax_caches, evals,
            rounds, ring_scores, edge_masks, parallel_wall, t0)

    rounds = 0
    go = True
    while go and rounds < max_rounds:
        new_graphs: List[np.ndarray] = []
        new_scores: List[float] = []
        proc_walls: List[float] = []
        for i in range(k):
            tp = time.perf_counter()
            pred = graphs[(i - 1) % k]
            if rounds == 0:
                init = np.zeros((n, n), dtype=np.int8)
            else:
                init = fusion.fusion_edge_union(
                    graphs[i], pred, engine=fusion_engine).astype(np.int8)
            if engine == "jax":
                out = ges_jit(
                    data_j, ar_j, jnp.asarray(init),
                    jnp.asarray(edge_masks[i].astype(np.int8)),
                    add_limit=add_limit, config=config, r_max=r_max,
                    pid_table=pid_j[i], cache=jax_caches[i],
                    return_cache=config.family_cache)
                if config.family_cache:
                    adj_i, score_i, n_ins, n_del, jax_caches[i] = out
                else:
                    adj_i, score_i, n_ins, n_del = out
                adj_i = np.asarray(adj_i)
                score_i = float(score_i)
                W = int(pid_j.shape[2])
                evals += W * n + W * (int(n_ins) + int(n_del))
            else:
                res = ges_host(data, arities, init_adj=init,
                               allowed=edge_masks[i], add_limit=add_limit,
                               config=config, cache=cache,
                               family_cache=dev_cache)
                adj_i, score_i = res.adj, res.score
                evals += res.n_score_evals
            new_graphs.append(adj_i)
            new_scores.append(score_i)
            proc_walls.append(time.perf_counter() - tp)
        graphs = new_graphs
        rounds += 1
        parallel_wall += max(proc_walls)   # ring processes run concurrently

        # ---- convergence check (Algorithm 1 lines 11-16) ------------------
        round_best = max(new_scores)
        ring_scores.append(round_best)
        if round_best > best_score + config.tol:
            best_score = round_best
            best_adj = graphs[int(np.argmax(new_scores))].copy()
            best_graphs = np.stack(graphs)
            best_graph_scores = np.asarray(new_scores)
            go = True
        else:
            go = False

    res = _finish_cges(
        data, arities, data_j, ar_j, r_max, best_adj,
        config, engine, cache, dev_cache, jax_caches, evals,
        rounds, ring_scores, edge_masks, parallel_wall, t0)
    res.ring_graphs, res.ring_graph_scores = best_graphs, best_graph_scores
    return res


def _finish_cges(data, arities, data_j, ar_j, r_max, best_adj,
                 config, engine, cache, dev_cache, jax_caches, evals,
                 rounds, ring_scores, edge_masks, parallel_wall,
                 t0) -> CGESResult:
    """Stage 3 (unrestricted fine-tuning GES from the ring winner) plus
    result assembly — shared by the lockstep round loop and the async-ring
    engine.  The compiled engines ("jax", "async") fine-tune with ges_jit;
    the host engine reuses its shared caches."""
    n = data.shape[1]
    t_ft = time.perf_counter()
    if engine in ("jax", "async"):
        adj_f, score_f, n_ins, n_del = ges_jit(
            data_j, ar_j, jnp.asarray(best_adj.astype(np.int8)),
            jnp.ones((n, n), dtype=jnp.int8),
            add_limit=None, config=config, r_max=r_max)
        final_adj = np.asarray(adj_f)
        final_score = float(score_f)
        evals += n * n + n * (int(n_ins) + int(n_del))
    else:
        res = ges_host(data, arities, init_adj=best_adj, allowed=None,
                       add_limit=None, config=config, cache=cache,
                       family_cache=dev_cache)
        final_adj, final_score = res.adj, res.score
        evals += res.n_score_evals

    parallel_wall += time.perf_counter() - t_ft       # fine-tune is serial
    fc_stats = None
    if dev_cache is not None:
        fc_stats = dev_cache.stats()
    elif config.family_cache and engine == "jax":
        hits = sum(int(c.hits) for c in jax_caches if c is not None)
        misses = sum(int(c.misses) for c in jax_caches if c is not None)
        fc_stats = {"hits": hits, "misses": misses,
                    "hit_rate": hits / max(hits + misses, 1)}
    return CGESResult(
        adj=final_adj, score=final_score, rounds=rounds,
        n_score_evals=evals, wall_time_s=time.perf_counter() - t0,
        ring_scores=ring_scores, edge_masks=edge_masks,
        parallel_wall_s=parallel_wall, family_cache_stats=fc_stats,
    )
