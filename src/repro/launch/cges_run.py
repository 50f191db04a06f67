"""cGES driver — the paper's end-to-end workload with fault tolerance.

    PYTHONPATH=src python -m repro.launch.cges_run \
        --family link_like --scale 0.05 --k 4 --limit --ckpt-dir /tmp/cges

Engines:
* ``--engine host`` (default): the checkpointable host round loop below —
  ring processes are host tasks with jit-batched W-wide column sweeps.
* ``--engine ring``: the fully-compiled shard_map ring (core/ring.ring_cges)
  on a k-device mesh (host platform devices are forced to k when needed),
  with per-process static (n, W) pid_tables so every compiled round pays
  W = |E_i|-wide sweeps; the unrestricted fine-tune still runs on host.

Fusion on the host driver goes through the unified engine in
``core/fusion.py``: ``--fusion-engine {host,jit}`` (default from
REPRO_FUSION_ENGINE) picks the numpy or traceable implementation of the
per-round sigma-consistent edge union — the ring engine always traces the
same layer inside its compiled program.

Fault tolerance (1000-node posture, per DESIGN.md; host engine only):
* round-atomic checkpointing of the full ring state (k graphs + best score):
  a killed run resumes at the last completed round with identical results
  (the ring is deterministic given the partition);
* elastic ring repair: ``--fail-at-round R --fail-member i`` simulates a
  member loss; its edge subset E_i is re-merged into its ring predecessor
  (partition.remerge_failed) and the ring continues with k-1 members — the
  subsets stay a disjoint cover of E, so cGES's guarantees are unaffected.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from ..core import (DeviceFamilyCache, GESConfig, ScoreCache, bdeu, fusion,
                    ges_host, partition)
from ..core.cges import edge_add_limit
from ..core.dag import smhd_np
from ..data.bn import benchmark_bn, forward_sample
from . import devices
from .compile_cache import enable_compile_cache


def ring_rounds(data, arities, edge_masks, config, add_limit, max_rounds,
                ckpt_dir=None, fail_at_round=None, fail_member=None,
                cache=None, verbose=True, fusion_engine=None,
                family_cache=None):
    """The learning stage as an explicit, checkpointable round loop.

    ``fusion_engine`` picks the host or traceable implementation of the
    unified sigma-consistent edge union (core/fusion.py) — identical
    adjacencies either way; ``None`` defaults from REPRO_FUSION_ENGINE.
    ``family_cache``: optional shared DeviceFamilyCache handle — the
    device-resident persistent column cache every member/round consults
    (trajectory-identical; see core/score_cache).
    """
    fusion_engine = fusion.resolve_fusion_engine(fusion_engine)
    k0, n, _ = edge_masks.shape
    graphs = [np.zeros((n, n), dtype=np.int8) for _ in range(edge_masks.shape[0])]
    best_score, best_adj = -np.inf, np.zeros((n, n), dtype=np.int8)
    start_round = 0
    cache = cache if cache is not None else ScoreCache()

    if ckpt_dir:
        os.makedirs(ckpt_dir, exist_ok=True)
        state_f = os.path.join(ckpt_dir, "ring_state.npz")
        if os.path.exists(state_f):
            z = np.load(state_f, allow_pickle=False)
            graphs = [z[f"g{i}"] for i in range(int(z["k"]))]
            edge_masks = z["masks"]
            best_score = float(z["best_score"])
            best_adj = z["best_adj"]
            start_round = int(z["round"])
            if verbose:
                print(f"resumed ring at round {start_round} (k={len(graphs)})")

    rnd = start_round
    go = True
    while go and rnd < max_rounds:
        k = edge_masks.shape[0]
        if fail_at_round is not None and rnd == fail_at_round and k > 1:
            fm = fail_member % k
            if verbose:
                print(f"[fault] member {fm} lost at round {rnd}: "
                      f"re-merging E_{fm} into its ring predecessor")
            edge_masks = partition.remerge_failed(edge_masks, fm)
            graphs.pop(fm)
            k -= 1
        new_graphs, new_scores = [], []
        for i in range(k):
            pred = graphs[(i - 1) % k]
            init = (np.zeros((n, n), dtype=np.int8) if rnd == 0
                    else fusion.fusion_edge_union(
                        graphs[i], pred, engine=fusion_engine).astype(np.int8))
            res = ges_host(data, arities, init_adj=init,
                           allowed=edge_masks[i], add_limit=add_limit,
                           config=config, cache=cache,
                           family_cache=family_cache)
            new_graphs.append(res.adj)
            new_scores.append(res.score)
        graphs = new_graphs
        rnd += 1
        round_best = max(new_scores)
        if round_best > best_score + config.tol:
            best_score = round_best
            best_adj = graphs[int(np.argmax(new_scores))].copy()
        else:
            go = False
        if verbose:
            print(f"round {rnd}: best BDeu {best_score:.2f} "
                  f"(round {round_best:.2f}, k={k})")
        if ckpt_dir:
            # np.savez appends .npz to names lacking it — keep the suffix
            tmp = os.path.join(ckpt_dir, "ring_state_tmp.npz")
            np.savez(tmp, k=len(graphs), masks=edge_masks,
                     best_score=best_score, best_adj=best_adj, round=rnd,
                     **{f"g{i}": g for i, g in enumerate(graphs)})
            os.replace(tmp, state_f)
    return best_adj, best_score, rnd, edge_masks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="link_like",
                    choices=["link_like", "pigs_like", "munin_like"])
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--m", type=int, default=2000)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--limit", action="store_true")
    ap.add_argument("--max-rounds", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--counts-impl", default="segment",
                    choices=["segment", "onehot", "pallas", "fused",
                             "fused_pallas"],
                    help="sweep-engine backend (core/sweeps): loop engines "
                         "build one table per candidate; fused* build one "
                         "joint contraction per insert column and one "
                         "marginalized family table per delete column — on "
                         "this host-engine driver both are restricted to "
                         "each process's E_i candidates (pids) before they "
                         "run")
    ap.add_argument("--engine", default="host", choices=["host", "ring"],
                    help="host: checkpointable host round loop; ring: the "
                         "fully-compiled shard_map ring with per-process "
                         "(n, W) pid_tables — compiled per-round sweep cost "
                         "tracks W = |E_i|, not n")
    ap.add_argument("--fusion-engine", default=None, choices=["host", "jit"],
                    help="engine for the per-round sigma-consistent edge "
                         "union on the host driver (core/fusion.py — the "
                         "same layer the compiled ring traces); default "
                         "reads REPRO_FUSION_ENGINE, else host.  Identical "
                         "adjacencies either way")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="shard the instance (m) axis over this many devices "
                         "— each device contracts m/d rows into the count "
                         "tables and ONE psum merges them before the cheap "
                         "BDeu reduction (table-identical to 1).  host "
                         "engine: every sweep runs on a d-device data mesh; "
                         "ring engine: the mesh becomes 2-D (ring k x data "
                         "d) and needs k*d devices")
    ap.add_argument("--family-cache", action="store_true",
                    help="persistent device-resident family-score cache "
                         "(core/score_cache): memoises (child, parent-set) "
                         "columns across GES iterations, rounds and ring "
                         "members with prioritized eviction; trajectories "
                         "stay bitwise-identical.  Also via "
                         "REPRO_FAMILY_CACHE=1")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fail-at-round", type=int, default=None)
    ap.add_argument("--fail-member", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.engine == "ring" and (args.ckpt_dir or args.fail_at_round
                                  is not None):
        ap.error("--ckpt-dir / --fail-at-round are host-engine features")
    if args.data_shards < 1:
        ap.error("--data-shards must be >= 1")
    # Device requirement: the compiled ring needs k devices on its ring
    # axis, times d when the data axis is on; the host engine needs d for
    # its per-sweep data mesh (launch/devices.py re-execs once with forced
    # host devices when the initialized platform is too small).
    need = (args.k * args.data_shards if args.engine == "ring"
            else args.data_shards)
    devices.force_host_devices_or_reexec(need, "repro.launch.cges_run")
    enable_compile_cache()

    t0 = time.time()
    bn = benchmark_bn(args.family, scale=args.scale, seed=args.seed)
    data = forward_sample(bn, args.m, np.random.default_rng(args.seed + 1))
    n = bn.n
    print(f"{args.family} scale={args.scale}: n={n}, m={args.m}")

    config = GESConfig(max_q=1024, counts_impl=args.counts_impl,
                       data_shards=(args.data_shards
                                    if args.engine == "host" else 1),
                       family_cache=(args.family_cache
                                     or GESConfig().family_cache))
    masks = partition.partition_edges(data, bn.arities, args.k)
    lim = edge_add_limit(n, args.k) if args.limit else None
    cache = ScoreCache()
    family_cache = (DeviceFamilyCache(n, config.cache_capacity)
                    if config.family_cache else None)

    ring_w = None
    ring_cache_stats = None
    if args.engine == "ring":
        from ..core.ring import RingSpec, ring_cges
        from .mesh import make_ring_data_mesh

        d = args.data_shards
        pid_tables = partition.pid_tables(masks)
        ring_w = int(pid_tables.shape[2])
        mesh = make_ring_data_mesh(args.k, d)
        spec = (RingSpec(k=args.k, max_rounds=args.max_rounds,
                         data_axis="data", data_axis_size=d) if d > 1
                else RingSpec(k=args.k, max_rounds=args.max_rounds))
        out_ring = ring_cges(
            data, bn.arities, masks, mesh, spec, config,
            add_limit=lim, pid_tables=pid_tables,
            return_cache_stats=config.family_cache)
        graphs, scores, rounds = out_ring[0], out_ring[1], out_ring[2]
        if config.family_cache:
            ring_cache_stats = out_ring[3]
        adj = graphs[int(np.argmax(scores))]
        print(f"compiled ring: {rounds} rounds, W={ring_w} "
              f"(restricted sweep width vs n={n}, data shards={d})")
    else:
        adj, score, rounds, masks = ring_rounds(
            data, bn.arities, masks, config, lim, args.max_rounds,
            ckpt_dir=args.ckpt_dir, fail_at_round=args.fail_at_round,
            fail_member=args.fail_member, cache=cache,
            fusion_engine=args.fusion_engine, family_cache=family_cache)

    # fine-tuning pass (unrestricted GES) — carries GES's guarantees
    res = ges_host(data, bn.arities, init_adj=adj, allowed=None,
                   add_limit=None, config=config, cache=cache,
                   family_cache=family_cache)
    wall = time.time() - t0
    out = {
        "family": args.family, "n": n, "m": args.m, "k": args.k,
        "engine": args.engine,
        "limit": bool(args.limit), "rounds": rounds,
        "bdeu_per_instance": res.score / args.m,
        "smhd_vs_truth": smhd_np(res.adj, bn.adj),
        "wall_s": round(wall, 2),
        "cache_hits": cache.hits, "cache_misses": cache.misses,
        "data_shards": args.data_shards,
    }
    if ring_w is not None:
        out["ring_W"] = ring_w
    if family_cache is not None:
        out["family_cache"] = family_cache.stats()
    if ring_cache_stats is not None:
        out["ring_family_cache"] = ring_cache_stats
    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
