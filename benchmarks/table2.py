"""Paper Table 2 (a: BDeu, b: SMHD, c: time) — all 8 algorithm configs on
family-matched synthetic link/pigs/munin-like networks.

Full paper scale (n=724/441/1041, m=5000, 11 replicas) is a CPU-week on this
container; the default `--scale` keeps the *structure statistics* of each
family (edge/node ratio, arities, max parents) at a tractable n.  All
algorithm code paths are identical to full scale — n is just a config.

Reported per (family, algorithm): normalized BDeu (Table 2a), SMHD vs the
true structure (2b), wall seconds + score-evaluation count (2c; evals are the
machine-independent cost the paper's CPU-time column proxies).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _peek_data_shards(argv):
    for i, a in enumerate(argv):
        if a == "--data-shards" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--data-shards="):
            return int(a.split("=", 1)[1])
    return 1


# --data-shards d runs every sweep on a d-device data-axis mesh
# (core/sweeps): XLA_FLAGS must be set before the backend initializes,
# which importing repro.core below does — hence this pre-import argv peek.
_d = _peek_data_shards(sys.argv[1:])
if _d > 1 and "host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_d}").strip()

import numpy as np

from repro.core import GESConfig, ScoreCache, cges, fges_host, ges_host
from repro.core.dag import smhd_np
from repro.data.bn import benchmark_bn, forward_sample
from repro.launch.compile_cache import enable_compile_cache

ALGOS = ["fGES", "GES", "cGES-2", "cGES-4", "cGES-8",
         "cGES-L-2", "cGES-L-4", "cGES-L-8"]


def run_algo(name: str, data, arities, config) -> dict:
    t0 = time.perf_counter()
    if name == "GES":
        r = ges_host(data, arities, config=config, cache=ScoreCache())
        adj, score, evals = r.adj, r.score, r.n_score_evals
        extra = {}
    elif name == "fGES":
        r = fges_host(data, arities, config=config)
        adj, score, evals = r.adj, r.score, r.n_score_evals
        extra = {}
    else:
        k = int(name.split("-")[-1])
        limit = "-L-" in name
        r = cges(data, arities, k=k, limit=limit, config=config)
        adj, score, evals = r.adj, r.score, r.n_score_evals
        extra = {"rounds": r.rounds}
    return dict(adj=adj, score=score, evals=evals,
                wall_s=time.perf_counter() - t0, **extra)


def bench(families, scale: float, m: int, seeds, algos=ALGOS, verbose=True,
          data_shards: int = 1):
    rows = []
    for fam in families:
        for seed in seeds:
            bn = benchmark_bn(fam, scale=scale, seed=seed)
            data = forward_sample(bn, m, np.random.default_rng(seed + 100))
            config = GESConfig(max_q=1024, data_shards=data_shards)
            for algo in algos:
                r = run_algo(algo, data, bn.arities, config)
                row = {
                    "family": fam, "seed": seed, "algo": algo, "n": bn.n,
                    "m": m,
                    "bdeu_per_inst": r["score"] / m,
                    "smhd": smhd_np(r["adj"], bn.adj),
                    "wall_s": round(r["wall_s"], 2),
                    "score_evals": r["evals"],
                }
                rows.append(row)
                if verbose:
                    print(f"  {fam:12s} seed{seed} {algo:9s} "
                          f"BDeu/м={row['bdeu_per_inst']:9.4f} "
                          f"SMHD={row['smhd']:4d} t={row['wall_s']:7.2f}s "
                          f"evals={row['score_evals']}")
    return rows


def summarize(rows):
    """Per (family, algo) means — the three sub-tables of Table 2."""
    import collections
    acc = collections.defaultdict(list)
    for r in rows:
        acc[(r["family"], r["algo"])].append(r)
    out = []
    for (fam, algo), rs in sorted(acc.items()):
        out.append({
            "family": fam, "algo": algo,
            "bdeu_per_inst": float(np.mean([r["bdeu_per_inst"] for r in rs])),
            "smhd": float(np.mean([r["smhd"] for r in rs])),
            "wall_s": float(np.mean([r["wall_s"] for r in rs])),
            "score_evals": float(np.mean([r["score_evals"] for r in rs])),
        })
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.055)
    ap.add_argument("--m", type=int, default=1500)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--families", nargs="+",
                    default=["pigs_like", "link_like", "munin_like"])
    ap.add_argument("--data-shards", type=int, default=1,
                    help="shard every sweep's instance axis over this many "
                         "(forced-host) devices with psum'd count tables — "
                         "table-identical results, per-device HBM traffic "
                         "and contraction flops scale by 1/d (see "
                         "repro.launch.roofline.sweep_data_axis_terms)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    rows = bench(args.families, args.scale, args.m, list(range(args.seeds)),
                 data_shards=args.data_shards)
    summary = summarize(rows)
    print("\n=== Table 2 summary (means over seeds) ===")
    print(f"{'family':12s} {'algo':9s} {'BDeu/m':>10s} {'SMHD':>7s} "
          f"{'time(s)':>8s} {'evals':>10s}")
    for s in summary:
        print(f"{s['family']:12s} {s['algo']:9s} {s['bdeu_per_inst']:10.4f} "
              f"{s['smhd']:7.1f} {s['wall_s']:8.2f} {s['score_evals']:10.0f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return summary


if __name__ == "__main__":
    main()
