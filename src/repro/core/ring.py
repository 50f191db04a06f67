"""The ring executor — cGES's learning stage as ONE compiled multi-device
program (shard_map over a "ring" mesh axis).

Mapping of the paper's distributed system onto JAX:

  * k ring processes        ->  k devices (or device groups) on a mesh axis
  * "send BN to successor"  ->  jax.lax.ppermute of the (n, n) int8 adjacency
  * BN fusion               ->  core/fusion.fuse_trace: the traceable engine
                                of the UNIFIED fusion layer (GHO ordering +
                                covered-edge-reversal sink conversion, one
                                maintained longest-path depth vector,
                                vmap-batched sigma transforms) — the same
                                code the host driver dispatches to, not a
                                hand-mirrored copy; this module keeps no
                                fusion math of its own (only re-exports)
  * constrained GES         ->  ges.ges_jit_body (lax.while_loop program);
                                every candidate rescoring inside it — FES
                                insert and BES delete columns alike — goes
                                through the unified core/sweeps engine, so a
                                fused counts_impl fuses BOTH phases of every
                                ring process (insert: one contraction per
                                column; delete: one family-table build per
                                column, marginalized per parent slot)
  * restricted E_i sweeps   ->  a static per-process (n, W) pid_table
                                (partition.pid_tables) rides the ring axis
                                next to the edge masks; ges_jit_body then
                                runs its whole while_loop in (W, n) index
                                space, so each compiled process pays
                                W = |E_i|-wide sweeps per round — the
                                paper's cost argument, end-to-end compiled
                                (restricted=False keeps the old
                                full-n-sweep-then-mask program)
  * convergence check       ->  lax.pmax over per-device best scores

The entire learning stage — all rounds, all k processes — is a single
jit-compiled program; one host call runs cGES's stage 2 to convergence.
This is also the program that is `.lower().compile()`d on the production
(16, 16) and (2, 16, 16) meshes by launch/dryrun.py (arch id: ``cges_ring``).

This lockstep program is the TRAJECTORY ORACLE: every round is a global
barrier (ppermute -> fuse -> sweep -> pmax), which makes it bitwise
reproducible but also means neighbor transfer never overlaps compute and
the slowest member stalls the whole ring.  The asynchronous multi-process
path (``core/ring_async.py``, ``cges(engine="async")``,
``launch/ring_async_run.py``) relaxes exactly the barrier column of the
mapping while keeping each member's compute identical:

  * k ring processes        ->  k OS processes (or threads), each running
                                the SAME ges_jit restricted sweep
  * "send BN to successor"  ->  a length-prefixed socket frame posted the
                                moment the sweep finishes; a round-keyed
                                double-buffered mailbox lets the transfer
                                overlap the successor's (W, n) sweep
  * BN fusion               ->  the same unified core/fusion layer, on the
                                receiving member, off the mailbox
  * convergence check       ->  a token circulating the ring (one lap
                                collects every member's round score; the
                                verdict lap commits or stops), with a
                                bounded speculation window instead of pmax
  * membership              ->  ELASTIC: heartbeat failure detection, the
                                dead member's E_i folded into its ring
                                predecessor (partition.remerge_failed
                                semantics), ring re-stitched so k-1
                                members finish the run

Healthy async runs replay the lockstep trajectory exactly (speculative
rounds never diverge because fuse/GES inputs don't depend on verdicts);
the oracle here is what the async tests pin against.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import bdeu, partition, score_cache
from .ges import GESConfig, ges_jit_body, trace_q_buckets, trace_steps
from .sweeps import pad_data_rows
# Fusion lives in ONE place (core/fusion.py); the compat names below are
# re-exported because pre-unification callers imported them from here.
from .fusion import (fuse_trace, fuse_jit, gho_order_jit,  # noqa: F401
                     sigma_consistent_jit)

Array = jax.Array
BIG = jnp.float32(3.0e38)


# ---------------------------------------------------------------------------
# The ring program
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RingSpec:
    k: int                       # ring size (devices along the ring axis)
    axis: str = "ring"           # mesh axis (or tuple) carrying the ring
    max_rounds: int = 16
    axis_model: Optional[str] = None   # optional scoring-TP axis inside each
    axis_model_size: int = 1           # ring process (production mesh: 'model')
    data_axis: Optional[str] = None    # optional instance-axis mesh dim: each
    data_axis_size: int = 1            # device scores its m/d rows + one psum


def _ring_body(data, arities, edge_mask, init_g, pid_table=None,
               *, spec: RingSpec, config: GESConfig, r_max: int,
               add_limit: int, q_buckets: bool = False):
    """Per-device body under shard_map.  edge_mask/init_g: (1, n, n) local;
    pid_table: optional (1, n, W) local — this process's static E_i candidate
    table, making every sweep of every round W-wide (see ges_jit_body).

    When ``spec.data_axis`` is set, ``data`` arrives as the local (m/d, n)
    row shard and every count build inside ges_jit_body psums over that
    axis (see core/sweeps).  When ``config.family_cache`` is set, a
    per-ring-process family-score cache is threaded through the rounds
    while_loop, so a family scored in round t (or inherited from a
    predecessor's graph) is never recontracted in round t' > t; the body
    then also returns the final (hits, misses) counters.  With
    ``q_buckets`` it next returns the (1, max_rounds, n_buckets)
    ``ges.q_buckets`` histogram of each round's GES (see ges_jit_body).
    Last it returns the (1, max_rounds, 2) insertions and deletions this
    process applied in each round (zeros past the executed rounds, in
    both).
    """
    axis = spec.axis
    k = spec.k
    n = init_g.shape[1]
    edge_mask = edge_mask[0]
    g0 = init_g[0]
    pids = None if pid_table is None else pid_table[0]

    perm = [(i, (i + 1) % k) for i in range(k)]  # send to successor
    use_cache = bool(config.family_cache)

    def one_round(g_own, cache):
        g_pred = jax.lax.ppermute(g_own, axis, perm)
        fused = fuse_trace(g_own, g_pred)
        out = ges_jit_body(
            data, arities, fused, edge_mask,
            jnp.int32(add_limit),
            config.ess, config.max_parents, config.max_q, r_max,
            config.counts_impl, config.tol, config.incremental,
            config.child_chunk,
            axis_model=spec.axis_model,
            axis_model_size=spec.axis_model_size,
            pid_table=pids,
            data_axis_name=spec.data_axis,
            cache=cache)
        if use_cache:
            cache = out[5]
        return out[0], out[1], jnp.stack(out[2:4]), out[4], cache

    def cond(state):
        go, rnd = state[4], state[5]
        return go & (rnd < spec.max_rounds)

    def body(state):
        g, g_best, s_best, best, go, rnd, steps, qbs = state[:8]
        cache = state[8] if use_cache else None
        adj, score, n_steps, qb, cache = one_round(g, cache)
        round_best = jax.lax.pmax(score, axis)
        improved = round_best > best + config.tol
        # Keep the graphs of the last GLOBALLY-improving round (Algorithm 1
        # holds onto the best BN): the final non-improving round's graphs
        # are discarded, exactly like the host driver's best_adj, so both
        # engines hand the same winner to the fine-tune pass.
        g_keep = jnp.where(improved, adj, g_best)
        s_keep = jnp.where(improved, score, s_best)
        out = (adj, g_keep, s_keep, jnp.maximum(best, round_best),
               improved, rnd + 1, steps.at[rnd].set(n_steps),
               qbs.at[rnd].set(qb))
        return out + (cache,) if use_cache else out

    n_buckets = len(bdeu.q_ladder(config.max_q))
    state0 = (g0, g0, -BIG, -BIG, jnp.bool_(True), jnp.int32(0),
              jnp.zeros((spec.max_rounds, 2), jnp.int32),
              jnp.zeros((spec.max_rounds, n_buckets), jnp.int32))
    if use_cache:
        width = n if pids is None else pids.shape[1]
        state0 = state0 + (score_cache.init(n, width, config.cache_capacity),)
    out = jax.lax.while_loop(cond, body, state0)
    res = (out[1][None], out[2][None], out[5])
    if use_cache:
        cache = out[8]
        res += (jnp.stack([cache.hits, cache.misses])[None],)  # (1, 2)
    if q_buckets:
        res += (out[7][None],)
    return res + (out[6][None],)


def build_ring_program(mesh: Mesh, spec: RingSpec, config: GESConfig,
                       r_max: int, add_limit: int, restricted: bool = False,
                       q_buckets: bool = False):
    """Compile-ready cGES stage-2 program for an arbitrary mesh.

    The ring axis is ``spec.axis``; data/arities are replicated, edge masks
    and graph state are sharded one-per-ring-slot.  Returns a function
    (data, arities, edge_masks, init_graphs) -> (graphs, scores, rounds);
    with ``restricted=True`` the program takes a fifth (k, n, W) int32
    ``pid_tables`` input (partition.pid_tables — one shared static W) and
    every ring process sweeps W-wide instead of full-n-then-mask.

    With ``spec.data_axis`` set (a SECOND mesh axis, orthogonal to the
    ring), the data rows are sharded ``P(data_axis, None)`` so each of the
    k * d devices contracts m/d instances and psums the count tables; the
    caller owns sentinel-padding ragged m (sweeps.pad_data_rows — ring_cges
    does it).  With ``config.family_cache`` the program returns a fourth
    (k, 2) int32 output: per-ring-process (hits, misses) cache counters.
    ``q_buckets=True`` (``ring_cges`` sets it) adds the (k, max_rounds,
    n_buckets) int32 ``ges.q_buckets`` histograms of each ring process in
    each round.  The last output is always the (k, max_rounds, 2) int32
    insertions and deletions of each ring process in each round.
    """
    axis = spec.axis

    body = partial(_ring_body, spec=spec, config=config, r_max=r_max,
                   add_limit=add_limit, q_buckets=q_buckets)

    data_spec = P() if spec.data_axis is None else P(spec.data_axis, None)
    pid_specs = (P(axis, None, None),) if restricted else ()
    stat_specs = (P(axis, None),) if config.family_cache else ()
    if q_buckets:
        stat_specs += (P(axis, None, None),)
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(data_spec, P(), P(axis, None, None), P(axis, None, None))
        + pid_specs,
        out_specs=(P(axis, None, None), P(axis), P()) + stat_specs
        + (P(axis, None, None),),
        check_vma=False,
    )
    return jax.jit(mapped)


def ring_cges(
    data: np.ndarray,
    arities: np.ndarray,
    edge_masks: np.ndarray,
    mesh: Mesh,
    spec: RingSpec,
    config: Optional[GESConfig] = None,
    add_limit: Optional[int] = None,
    restricted: bool = True,
    pid_tables: Optional[np.ndarray] = None,
    return_cache_stats: bool = False,
):
    """Execute the compiled ring on a real mesh (k devices).

    Returns the per-process (graphs, scores) of the last *globally
    improving* round — the best BNs Algorithm 1 keeps, identical to the
    host driver's ``best_adj`` selection — plus the executed round count
    (which includes the final non-improving round).

    ``restricted=True`` (default) derives per-process (n, W) pid tables from
    the edge masks (or takes them via ``pid_tables``) so each compiled
    process pays W = |E_i|-wide sweeps; ``restricted=False`` runs the old
    full-n-masked program (same trajectories, n-wide per-round cost).

    ``spec.data_axis`` shards the instance axis across a second mesh dim
    (rows are sentinel-padded here when m % d != 0 — exact, see
    sweeps.pad_data_rows).  ``return_cache_stats=True`` (requires
    ``config.family_cache``) appends a list of per-process stats dicts
    (hits / misses / hit_rate) to the return tuple.
    """
    k, n, _ = edge_masks.shape
    if k != spec.k:
        # asserts vanish under ``python -O`` and the mismatch would
        # otherwise surface as an opaque shard_map shape error
        raise ValueError(
            f"edge_masks carries k={k} ring members but RingSpec.k="
            f"{spec.k} — the partition and the mesh spec must agree")
    config = config if config is not None else GESConfig()
    r_max = int(arities.max())
    lim = int(n * n if add_limit is None else add_limit)
    # Program spans (profiler trace only): ring.build ends with the
    # arguments on the device, ring.launch holds the trace, compile (or
    # cache load) and enqueue, ring.run the blocking readback.
    with TraceAnnotation("ring.build"):
        prog = build_ring_program(mesh, spec, config, r_max, lim,
                                  restricted=restricted, q_buckets=True)
        data = np.asarray(data)
        if spec.data_axis is not None and spec.data_axis_size > 1:
            data = np.asarray(pad_data_rows(data.astype(np.int32), r_max,
                                            spec.data_axis_size))
        graphs0 = jnp.zeros((k, n, n), dtype=jnp.int8)
        args = [
            jnp.asarray(data.astype(np.int32)),
            jnp.asarray(arities.astype(np.int32)),
            jnp.asarray(edge_masks.astype(np.int8)),
            graphs0,
        ]
        if restricted:
            if pid_tables is None:
                pid_tables = partition.pid_tables(edge_masks)
            args.append(jnp.asarray(np.asarray(pid_tables, dtype=np.int32)))
    with TraceAnnotation("ring.launch"):
        out = prog(*args)
    with TraceAnnotation("ring.run") as span:
        graphs, scores = np.asarray(out[0]), np.asarray(out[1])
        rounds = int(out[2])
        steps, q_buckets = np.asarray(out[-1]), np.asarray(out[-2])
        span.set_metadata(rounds=rounds)
    for r in range(rounds):
        for i in range(k):
            trace_steps(r, i, *steps[i, r])
            trace_q_buckets(r, i, q_buckets[i, r], config.max_q)
    if return_cache_stats:
        if not config.family_cache:
            raise ValueError("return_cache_stats requires config.family_cache")
        hm = np.asarray(out[3])
        stats = [{"hits": int(h), "misses": int(ms),
                  "hit_rate": float(h) / max(int(h) + int(ms), 1)}
                 for h, ms in hm]
        return graphs, scores, rounds, stats
    return graphs, scores, rounds
