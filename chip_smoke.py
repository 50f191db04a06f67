#!/usr/bin/env python3
"""Chip smoke test: cGES (Algorithm 1 of arXiv:2409.13314) end to end on a TPU.

    python3 chip_smoke.py             # one chip: cGES-L-4 through cges()
    python3 chip_smoke.py --chips 4   # four chips: the compiled ring only

Both phases learn the pigs-like domain at full width — n = 441 variables,
m = 5000 instances sampled from a seeded network (data/bn.py).

One chip.  Runs the paper's cGES-L-4 through ``cges(engine="jax")`` with the
compiled Pallas kernels (``counts_impl="fused_pallas"``) and checks it:
  * the kernels reproduce their jnp oracles exactly at this width;
  * the compiled restricted sweep holds the kernels (``tpu_custom_call``);
  * the same run with the jnp engine (``counts_impl="fused"``) takes the same
    trajectory: the same rounds, ring members' graphs and final DAG, and
    BDeu within 1e-6 relative (float32 summation order);
  * every family of the ring winner and of the final DAG scores within
    FAMILY_ATOL of the float64 host scorer, and so do their totals within
    SCORE_RTOL.

Four chips.  Runs ``ring_cges`` (one ring member per chip, restricted pid
tables) and checks that it places one member per chip and that its rounds,
the k member graphs it keeps, its winner and the fine-tuned DAG equal
``cges(engine="jax")`` run on one chip with the same edge partition.

The last line of stdout is the JSON result; it is printed only when every
check passed.  With no TPU, or when any phase fails, the script exits
non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

FAMILY = "pigs_like"
M = 5000
K = 4
MAX_Q = 1024
SEED = 0
# Ring rounds are capped so that both engines fit the run's time limit: the
# jnp reference engine's scatter-based counts take tens of seconds per round
# on a v5e chip, where the Pallas engine takes about two.
MAX_ROUNDS = 10
# A family's BDeu is a difference of lgamma sums many times its size, and the
# chip's float32 log/lgamma are less exact than the CPU's.  Bounds against the
# float64 host scorer, twice the largest gaps read on a v5e in this run with
# the kernels exact against their oracles: per family 0.535 BDeu units (a root
# family, where the counts and so the lgamma arguments are largest), on the
# ring winner's total 3.1e-5 relative (the final DAG's: 1.5e-5; the CPU's
# float32 total: 1.2e-7).  The per-family bound sees a fault confined to a
# few families, which the total would hide.
FAMILY_ATOL = 1.1
SCORE_RTOL = 6.5e-5


class SmokeFailure(Exception):
    pass


def same_bdeu(a: float, b: float) -> bool:
    """Equal up to float32 summation order: 1e-6 relative."""
    return a == b or abs(a - b) <= 1e-6 * abs(b)


def same_graphs(a, b) -> bool:
    import numpy as np

    return a is not None and b is not None and np.array_equal(a, b)


def ring_winner(res):
    """The graph the fine-tune started from: the best of the last improving
    round's member graphs."""
    import numpy as np

    return res.ring_graphs[int(np.argmax(res.ring_graph_scores))]


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events, so a timed run splits into compile and run time."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def timed(self, fn):
        c0, t0 = self.seconds, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        comp = self.seconds - c0
        return out, comp, wall - comp


def problem():
    import numpy as np

    from repro.data.bn import benchmark_bn, forward_sample

    bn = benchmark_bn(FAMILY, scale=1.0, seed=SEED)
    data = forward_sample(bn, M, np.random.default_rng(SEED + 1))
    return bn, data


def check_kernels(data, arities) -> None:
    """Compiled kernels == their jnp oracles, exactly, at this width."""
    import numpy as np
    import jax.numpy as jnp

    from repro.core import bdeu
    from repro.kernels.bdeu_count import contingency_counts
    from repro.kernels.bdeu_sweep import (delete_marginals,
                                          sweep_counts_restricted)

    dj, aj = jnp.asarray(data), jnp.asarray(arities)
    n, r = data.shape[1], int(arities.max())
    parents = [1, n // 3, n // 2]                # a three-parent family
    pm = np.zeros(n, bool)
    pm[parents] = True
    cfg, _ = bdeu._slot_encode(dj, aj, jnp.asarray(pm))
    child = dj[:, n - 1]
    pids = jnp.arange(0, n, 2, dtype=jnp.int32)  # W = n / 2, a ring member's
    slot_ar = jnp.asarray(arities[parents])
    slot_low = jnp.asarray(np.array(
        [arities[parents[1]] * arities[parents[2]], arities[parents[2]], 1],
        np.int32))
    kw = dict(max_q=MAX_Q, r_max=r)
    for name, run in (
            ("bdeu_count", lambda ref: contingency_counts(
                cfg, child, use_ref=ref, **kw)),
            ("bdeu_sweep insert (restricted W)", lambda ref:
             sweep_counts_restricted(cfg, child, dj, pids, use_ref=ref,
                                     **kw)),
            ("bdeu_sweep delete marginals", lambda ref: delete_marginals(
                cfg, child, slot_ar, slot_low, use_ref=ref, **kw))):
        got, want = np.asarray(run(False)), np.asarray(run(True))
        check(np.array_equal(got, want),
              f"{name} {got.shape} equals its jnp oracle exactly")


def restricted_sweep_text(data, arities, pid_table) -> str:
    """Compiled HLO of one restricted (W, n) insert sweep."""
    import jax.numpy as jnp

    from repro.core import sweeps

    n = data.shape[1]
    return sweeps._sweep_matrix_restricted.lower(
        jnp.asarray(data), jnp.asarray(arities),
        jnp.zeros((n, n), jnp.int8), jnp.asarray(pid_table),
        ess=10.0, max_q=MAX_Q, r_max=int(arities.max()),
        counts_impl="fused_pallas", kind="insert",
        child_chunk=None).compile().as_text()


def check_against_host(data, arities, adj, score: float, what: str) -> None:
    """Each family of ``adj`` as the device scores it (the compiled
    ``bdeu_count`` kernel), and the reported total ``score``, against the
    float64 host scorer (bdeu.local_score_np)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import bdeu

    n = adj.shape[0]
    device = np.asarray(jax.jit(
        bdeu.family_scores_batch, static_argnums=(4, 5, 6, 7))(
        jnp.asarray(data), jnp.asarray(arities),
        jnp.arange(n, dtype=jnp.int32), jnp.asarray(adj.astype(bool).T),
        10.0, MAX_Q, int(arities.max()), "pallas"),
        np.float64)
    host = np.array([bdeu.local_score_np(data, arities, y,
                                         np.flatnonzero(adj[:, y]))
                     for y in range(n)])
    gap = np.abs(device - host)
    worst = int(np.argmax(gap))
    print(f"{what}: family gap to float64 max {float(gap[worst])!r} (family "
          f"{worst}, {int(adj[:, worst].sum())} parents) mean "
          f"{float(gap.mean())!r}", flush=True)
    check(gap[worst] <= FAMILY_ATOL,
          f"{what}: every family within {FAMILY_ATOL} of the float64 host "
          f"scorer")
    score, exact = float(score), float(host.sum())
    rel = abs(score - exact) / abs(exact)
    print(f"{what}: reported BDeu {score!r} vs float64 {exact!r} "
          f"(relative {rel!r})", flush=True)
    check(rel <= SCORE_RTOL,
          f"{what}: reported BDeu within {SCORE_RTOL} of the float64 host "
          f"scorer")


def one_chip(clock) -> None:
    import numpy as np

    from repro.core import GESConfig, cges, partition
    from repro.core.dag import smhd_np

    bn, data = problem()
    n = bn.n
    print(f"domain {FAMILY}: n={n} m={M} k={K} (cGES-L-{K}, "
          f"max_q={MAX_Q}, max_rounds={MAX_ROUNDS})", flush=True)
    check(n == 441 and data.shape == (M, n), "full paper width")
    check_kernels(data, bn.arities)

    masks = partition.partition_edges(data, bn.arities, K)
    text = restricted_sweep_text(data, bn.arities,
                                 partition.pid_tables(masks)[0])
    check("tpu_custom_call" in text,
          "tpu_custom_call in the compiled restricted sweep")

    runs = {}
    for impl in ("fused_pallas", "fused"):
        res, comp, run = clock.timed(lambda: cges(
            data, bn.arities, k=K, limit=True, engine="jax",
            max_rounds=MAX_ROUNDS, edge_masks=masks,
            config=GESConfig(max_q=MAX_Q, counts_impl=impl)))
        runs[impl] = res
        print(f"{impl}: rounds={res.rounds} BDeu/m={res.score / M:.6f} "
              f"SMHD={smhd_np(res.adj, bn.adj)} edges={int(res.adj.sum())} "
              f"compile_s={comp:.1f} run_s={run:.1f}", flush=True)
        print(f"  ring scores: {res.ring_scores}", flush=True)

    p, f = runs["fused_pallas"], runs["fused"]
    print(f"bitwise equal scores: ring {p.ring_scores == f.ring_scores}, "
          f"final {p.score == f.score}", flush=True)
    same = (p.rounds == f.rounds
            and same_graphs(p.ring_graphs, f.ring_graphs)
            and np.array_equal(p.adj, f.adj)
            and len(p.ring_scores) == len(f.ring_scores)
            and all(map(same_bdeu,
                        p.ring_scores + list(p.ring_graph_scores) + [p.score],
                        f.ring_scores + list(f.ring_graph_scores) + [f.score])))
    check(same, "fused_pallas trajectory matches fused: same rounds, ring "
                "member graphs and DAG; BDeu within 1e-6 relative")
    check_against_host(data, bn.arities, ring_winner(p),
                       max(p.ring_graph_scores), "ring winner")
    check_against_host(data, bn.arities, p.adj, p.score, "final DAG")


def four_chips(clock) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import GESConfig, cges, ges_jit, partition
    from repro.core.cges import edge_add_limit
    from repro.core.dag import smhd_np
    from repro.core.ring import RingSpec, build_ring_program, ring_cges
    from repro.launch.mesh import make_ring_data_mesh

    check(len(jax.devices()) >= K, f"{K} chips present")
    bn, data = problem()
    n, r_max = bn.n, int(bn.arities.max())
    lim = edge_add_limit(n, K)
    config = GESConfig(max_q=MAX_Q, counts_impl="fused_pallas")
    masks = partition.partition_edges(data, bn.arities, K)
    pid_tables = partition.pid_tables(masks)
    print(f"domain {FAMILY}: n={n} m={M} k={K} ring on {K} chips "
          f"(max_rounds={MAX_ROUNDS})", flush=True)

    mesh = make_ring_data_mesh(K)
    spec = RingSpec(k=K, max_rounds=MAX_ROUNDS)
    # one member per chip: each member's graph and pid table live on their
    # own device in the compiled program the ring runs
    prog = build_ring_program(mesh, spec, config, r_max, lim,
                              restricted=True)
    args = (jnp.asarray(data), jnp.asarray(bn.arities.astype(np.int32)),
            jnp.asarray(masks.astype(np.int8)),
            jnp.zeros((K, n, n), jnp.int8), jnp.asarray(pid_tables))
    compiled = prog.lower(*args).compile()
    placement = compiled.output_shardings[0].devices_indices_map((K, n, n))
    members = sorted(idx[0].start for idx in placement.values())
    check(len(placement) == K and members == list(range(K)),
          f"one ring member per chip: {len(placement)} devices hold "
          f"member slices {members}")

    (graphs, scores, rounds), comp, run = clock.timed(lambda: ring_cges(
        data, bn.arities, masks, mesh, spec, config, add_limit=lim,
        pid_tables=pid_tables))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:K]]
    print(f"ring: rounds={rounds} best={float(scores.max())!r} "
          f"compile_s={comp:.1f} run_s={run:.1f} peak_bytes={peaks}",
          flush=True)
    check(all(p > 0 for p in peaks), "every chip held ring state")
    winner = graphs[int(np.argmax(scores))]
    adj_f, score_f, _, _ = ges_jit(
        jnp.asarray(data), jnp.asarray(bn.arities.astype(np.int32)),
        jnp.asarray(winner), jnp.ones((n, n), jnp.int8), add_limit=None,
        config=config, r_max=r_max)
    adj_f, score_f = np.asarray(adj_f), float(score_f)

    ref, comp, run = clock.timed(lambda: cges(
        data, bn.arities, k=K, limit=True, engine="jax",
        max_rounds=MAX_ROUNDS, edge_masks=masks, config=config))
    print(f"one-chip cges: rounds={ref.rounds} best={max(ref.ring_scores)!r} "
          f"compile_s={comp:.1f} run_s={run:.1f}", flush=True)
    print(f"member scores: ring {scores.tolist()} cges "
          f"{ref.ring_graph_scores.tolist()}", flush=True)
    print(f"fine-tuned: ring BDeu/m={score_f / M:.6f} "
          f"cges BDeu/m={ref.score / M:.6f} SMHD={smhd_np(adj_f, bn.adj)}",
          flush=True)
    check(rounds == ref.rounds, "same number of rounds")
    check(same_graphs(graphs, ref.ring_graphs),
          f"same {K} member graphs in the last improving round")
    check(all(map(same_bdeu, scores.tolist(), ref.ring_graph_scores)),
          "same member BDeu, within 1e-6 relative")
    check(same_graphs(winner, ring_winner(ref)), "same ring winner")
    check(np.array_equal(adj_f, ref.adj) and same_bdeu(score_f, ref.score),
          "same fine-tuned DAG; BDeu within 1e-6 relative")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: cGES-L-4 through cges() on one chip; 4: the "
                         "compiled ring across four chips, and the one-chip "
                         "run it must equal")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(clock)
        else:
            one_chip(clock)
    except SmokeFailure as e:
        print(f"smoke test failed: {e}", file=sys.stderr)
        return 1
    print(f"total compile_s={clock.seconds:.1f} "
          f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    used = devices[:args.chips]
    print(json.dumps({"ok": True, "device": {
        "platform": used[0].platform, "kind": used[0].device_kind,
        "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
