"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference, wall time on
CPU + analytic flops.  Interpret-mode timing measures correctness-path cost,
not TPU performance — the TPU-relevant numbers are the roofline terms in
EXPERIMENTS.md; this harness checks call overhead and validates shapes at
benchmark scale.

``--sweep-json PATH`` additionally times the fused all-candidate BDeu sweeps
against the per-candidate loop engine at paper scale — the FES insert column
(one joint contraction), the BES delete column (one family-table build,
marginalized per parent slot), the VMEM-resident Pallas delete column
(``delete_pallas``: table build + per-slot marginalization + BDeu reduction
in ONE kernel, with HBM-traffic accounting vs the two-step
build-then-marginalize path it replaced), the restricted-W ring column
(contraction gathered down to the W = |E_i| candidates before it runs) and
the compiled-ring per-round matrix (``ring_compiled``: the (W, n) pid_table
sweep the ges_jit/shard_map ring initializes each round from, vs the old
full-n matrix) — and writes a machine-readable trajectory record; later PRs
diff this file to track the sweep's perf over time.

The record also carries a ``fusion`` entry: the OTHER per-round ring
operator — the sigma-consistent edge union (core/fusion.py) — timed host vs
jit at the same n, against the pre-refactor traceable baseline that
recomputed the full longest-path depth per covered reversal (kept inline
below as ``_legacy_fuse_jit``), plus the fusion/sweep per-round cost ratio
that decides whether compiled ring rounds are sweep-bound or fusion-bound.

``async_ring`` records the asynchronous double-buffered ring
(core/ring_async): per-round wall vs the warm lockstep compiled ring on
the same seeded partition, the permute-wait/fuse/sweep phase breakdown
per member (blocked transfer wait vs sweep time is the overlap
evidence), trajectory parity, and a kill-one-member elastic run
converging with k-1 members.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# The data_sharded sweep entry times d-device data-axis meshes (d up to 4);
# XLA_FLAGS must be set before the backend initializes, so peek argv before
# the jax import (only when the sweep record was asked for — the plain
# kernel table keeps the default single-device platform).
if "--sweep-json" in " ".join(sys.argv[1:]) and \
        "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_all():
    rows = []
    key = jax.random.PRNGKey(0)

    # bdeu_count: paper-scale single-candidate table (m=5000, q=4096)
    from repro.kernels.bdeu_count import contingency_counts
    cfgv = jax.random.randint(key, (5000,), 0, 4096, dtype=jnp.int32)
    child = jax.random.randint(key, (5000,), 0, 4, dtype=jnp.int32)
    for impl, use_ref in (("pallas_interp", False), ("jnp_ref", True)):
        us = _time(lambda a, b: contingency_counts(
            a, b, max_q=4096, r_max=4, use_ref=use_ref), cfgv, child)
        rows.append((f"bdeu_count/{impl}", us,
                     "m=5000 q=4096 r=4; flops≈%.2e" % (2 * 5000 * 4096)))

    # bdeu_sweep: fused all-candidate sweep counts, pallas-interp vs jnp ref
    from repro.kernels.bdeu_sweep import sweep_counts
    ks = jax.random.split(key, 3)
    cfg0 = jax.random.randint(ks[0], (2560,), 0, 128, dtype=jnp.int32)
    childv = jax.random.randint(ks[1], (2560,), 0, 3, dtype=jnp.int32)
    datav = jax.random.randint(ks[2], (2560, 64), 0, 3, dtype=jnp.int32)
    for impl, use_ref in (("pallas_interp", False), ("jnp_ref", True)):
        us = _time(lambda a, b, c: sweep_counts(
            a, b, c, max_q=128, r_max=3, use_ref=use_ref), cfg0, childv, datav)
        rows.append((f"bdeu_sweep/{impl}", us,
                     "m=2560 n=64 q=128 r=3; flops≈%.2e"
                     % (2 * 2560 * 128 * 64 * 3)))

    # flash attention: one 1k x 1k head block
    from repro.kernels.flash_attention import flash_attention
    q = jax.random.normal(key, (1, 4, 1024, 64), jnp.float32)
    k = jax.random.normal(key, (1, 2, 1024, 64), jnp.float32)
    v = jax.random.normal(key, (1, 2, 1024, 64), jnp.float32)
    for impl, use_ref in (("pallas_interp", False), ("jnp_ref", True)):
        us = _time(lambda a, b, c: flash_attention(
            a, b, c, causal=True, use_ref=use_ref), q, k, v)
        rows.append((f"flash_attention/{impl}", us,
                     "B1 H4 T1k D64; flops≈%.2e" % (4 * 4 * 1024 * 1024 * 64)))

    # ssd scan: zamba-like chunk
    from repro.kernels.ssd_scan import ssd_scan
    x = jax.random.normal(key, (1, 4, 1024, 64), jnp.float32)
    a = -jax.nn.softplus(jax.random.normal(key, (1, 4, 1024)))
    b = jax.random.normal(key, (1, 4, 1024, 64)) * 0.3
    c = jax.random.normal(key, (1, 4, 1024, 64)) * 0.3
    for impl, use_ref in (("pallas_interp", False), ("jnp_ref", True)):
        us = _time(lambda *t: ssd_scan(*t, chunk=128, use_ref=use_ref),
                   x, a, b, c)
        rows.append((f"ssd_scan/{impl}", us, "B1 H4 T1k P64 N64"))
    return rows


def _legacy_fuse_jit(g_own, g_pred):
    """Pre-refactor traceable fusion (PR 3 state), kept ONLY as the benchmark
    baseline for ``fusion.speedup_jit_vs_prerefactor``: GHO cost re-summed
    from both (n, n) masks at every position, and a full longest-path depth
    recompute — an O(n)-step fori_loop over the whole matrix — inside every
    covered-edge reversal.  core/fusion.py's engines replaced both with
    incremental maintenance."""
    n = g_own.shape[0]

    def depth_full(adj, in_s):
        sub = adj.astype(bool) & in_s[:, None] & in_s[None, :]

        def body(_, depth):
            parent_d = jnp.where(sub, depth[:, None], -1)
            return jnp.where(in_s,
                             jnp.maximum(depth, parent_d.max(axis=0) + 1), -1)

        return jax.lax.fori_loop(0, n, body, jnp.where(in_s, 0, -1))

    def gho(adj_a, adj_b):
        a, b = adj_a.astype(jnp.int32), adj_b.astype(jnp.int32)

        def body(step, carry):
            rank, remaining = carry
            rem = remaining.astype(jnp.int32)
            cost = (a * rem[None, :]).sum(1) + (b * rem[None, :]).sum(1)
            cost = jnp.where(remaining, cost, jnp.iinfo(jnp.int32).max)
            v = jnp.argmin(cost)
            return rank.at[v].set(n - 1 - step), remaining.at[v].set(False)

        rank, _ = jax.lax.fori_loop(
            0, n, body, (jnp.zeros(n, jnp.int32), jnp.ones(n, bool)))
        return rank

    def sigma(adj, rank):
        order = jnp.argsort(-rank)

        def process_node(step, adj):
            v = order[step]
            in_s = rank <= rank[v]

            def cond(adj):
                return (jnp.take(adj, v, axis=0).astype(bool) & in_s).any()

            def body(adj):
                out = jnp.take(adj, v, axis=0).astype(bool) & in_s
                depth = depth_full(adj, in_s)
                w = jnp.argmin(jnp.where(out, depth,
                                         jnp.iinfo(jnp.int32).max))
                pa_v = jnp.take(adj, v, axis=1).astype(bool)
                pa_w = jnp.take(adj, w, axis=1).astype(bool)
                idx = jnp.arange(n)
                add_to_w = pa_v & ~pa_w & (idx != w) & (idx != v)
                add_to_v = pa_w & ~pa_v & (idx != v) & (idx != w)
                adj = adj.at[:, w].set((pa_w | add_to_w).astype(adj.dtype))
                pa_v2 = jnp.take(adj, v, axis=1).astype(bool)
                adj = adj.at[:, v].set((pa_v2 | add_to_v).astype(adj.dtype))
                return adj.at[v, w].set(0).at[w, v].set(1)

            return jax.lax.while_loop(cond, body, adj)

        return jax.lax.fori_loop(0, n, process_node, adj)

    rank = gho(g_own, g_pred)
    ta = sigma(g_own.astype(jnp.int8), rank)
    tb = sigma(g_pred.astype(jnp.int8), rank)
    return (ta.astype(bool) | tb.astype(bool)).astype(jnp.int8)


def bench_fusion(n: int = 400, seed: int = 0, reps: int = 3,
                 legacy: bool = True) -> dict:
    """Per-round ring fusion (sigma-consistent edge union) at paper scale.

    Times the unified engine (core/fusion.py) host vs jit on a sparse random
    DAG pair, and — when ``legacy`` — the pre-refactor traceable baseline
    (full depth recompute per reversal) for the recorded speedup.
    """
    from repro.core import fusion
    from repro.core.dag import random_dag_np

    rng = np.random.default_rng(seed)
    a = random_dag_np(rng, n, int(1.2 * n), max_parents=3)
    b = random_dag_np(rng, n, int(1.2 * n), max_parents=3)
    a8 = jnp.asarray(a.astype(np.int8))
    b8 = jnp.asarray(b.astype(np.int8))

    host_us = _time(lambda x, y: fusion.fusion_edge_union(x, y,
                                                          engine="host"),
                    a, b, reps=reps)
    jit_us = _time(jax.jit(fusion.fuse_trace), a8, b8, reps=reps)
    rec = {"n": n,
           "edges": {"a": int(a.sum()), "b": int(b.sum())},
           "host_us": round(host_us, 1),
           "jit_us": round(jit_us, 1)}
    if legacy:
        # The baseline is minutes-scale at n=400 — time it by hand with ONE
        # warmup + ONE rep (_time's warmup would execute it twice more).
        fn = jax.jit(_legacy_fuse_jit)
        jax.block_until_ready(fn(a8, b8))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(a8, b8))
        legacy_us = (time.perf_counter() - t0) * 1e6
        rec["legacy_jit_us"] = round(legacy_us, 1)
        rec["speedup_jit_vs_prerefactor"] = round(legacy_us / jit_us, 2)
    return rec


def bench_sweep(n: int = 400, m: int = 5000, max_q: int = 256,
                seed: int = 0, reps: int = 3, w: int = 32) -> dict:
    """Fused vs per-candidate-loop sweep columns at paper scale.

    Times one child's candidate columns through the unified engine
    (core/sweeps.sweep): the loop engine dispatches one contingency build
    per candidate; the fused engines dispatch

    * insert: ONE joint contraction (jnp: one segment-sum; kernel: r_max
      matmuls),
    * delete: ONE family-table build, every candidate table read off it by
      marginalizing one parent slot (zero re-counting),
    * restricted-W (ring E_i): the insert contraction gathered down to the W
      candidate columns BEFORE it runs — cost tracks W, not n.

    CPU wall time — the dispatch-count ratio is the hardware-independent
    part.
    """
    from repro.core.sweeps import sweep

    rng = np.random.default_rng(seed)
    arities = rng.integers(2, 4, size=n)
    data = np.stack([rng.integers(0, a, size=m) for a in arities], 1)
    adj = np.zeros((n, n), dtype=np.int8)
    adj[1, 0] = adj[2, 0] = 1          # child 0 with two parents (q0 <= 9)
    r_max = int(arities.max())
    dj = jnp.asarray(data.astype(np.int32))
    aj = jnp.asarray(arities.astype(np.int32))
    adjj = jnp.asarray(adj)
    kw = dict(ess=10.0, max_q=max_q, r_max=r_max)

    def col(kind, impl, pids=None):
        return _time(lambda a: sweep(dj, aj, a, kind=kind, y=0, pids=pids,
                                     counts_impl=impl, **kw), adjj, reps=reps)

    rec = {"n": n, "m": m, "max_q": max_q, "r_max": r_max,
           "platform": jax.default_backend(),
           # Static program-structure counts (not runtime counters): the loop
           # engine builds one (max_q, r_max) contingency table per candidate
           # (on TPU: n bdeu_count kernel launches per column); the fused
           # engine builds ALL candidate tables in one joint contraction (one
           # grid-batched bdeu_sweep launch / one segment-sum in the timed
           # jnp CPU mirrors below).
           "sweep_table_builds": {"loop_segment": n, "fused": 1},
           "dispatch_ratio": n,
           "engines": {}}
    for name, impl in (("loop_segment", "segment"), ("fused", "fused")):
        us = col("insert", impl)
        rec["engines"][name] = {
            "sweep_us": round(us, 1),
            "score_evals_per_s": round(n / (us * 1e-6), 1),
        }
    rec["speedup_fused_vs_loop"] = round(
        rec["engines"]["loop_segment"]["sweep_us"]
        / rec["engines"]["fused"]["sweep_us"], 2)

    # BES delete column: loop = n table builds; fused = ONE family-table
    # build + an O(n * max_q * r_max) marginalization, no re-counting.
    rec["delete"] = {"sweep_table_builds": {"loop_segment": n, "fused": 1},
                     "engines": {}}
    for name, impl in (("loop_segment", "segment"), ("fused", "fused"),
                       ("fused_pallas", "fused_pallas")):
        us = col("delete", impl)
        rec["delete"]["engines"][name] = {
            "sweep_us": round(us, 1),
            "score_evals_per_s": round(n / (us * 1e-6), 1),
        }
    rec["delete"]["speedup_fused_vs_loop"] = round(
        rec["delete"]["engines"]["loop_segment"]["sweep_us"]
        / rec["delete"]["engines"]["fused"]["sweep_us"], 2)

    # VMEM-resident Pallas delete column (kernels/bdeu_sweep.delete_marginals:
    # the one family table accumulates in VMEM scratch, every parent-slot
    # marginal is formed in VMEM and the (S + 1) tables are written, then
    # reduced by jnp) vs the two-step path it replaced — bdeu_count Pallas table
    # build, then jnp marginalization — which round-trips the (max_q, r_max)
    # table through HBM once per column.  Interpret-mode wall time measures
    # correctness-path cost; the HBM-byte accounting (analytic, logical f32
    # sizes) is the hardware-independent part.
    from repro.core import bdeu as _bdeu

    @jax.jit
    def two_step_delete_col(a):
        # counts_impl="pallas" routes fused_delete_scores through its
        # non-kernel branch: bdeu_count Pallas table build + jnp
        # marginalization — the EXACT two-step engine the VMEM kernel
        # replaced, so the baseline can never drift from the real path
        return _bdeu.fused_delete_scores(
            dj, aj, jnp.int32(0), a.astype(bool)[:, 0], 10.0, max_q, r_max,
            counts_impl="pallas")

    two_step_us = _time(two_step_delete_col, adjj, reps=reps)
    vmem_us = rec["delete"]["engines"]["fused_pallas"]["sweep_us"]
    table = 4 * max_q * r_max                      # logical f32 family table
    inputs = 8 * m                                 # cfg + child int32 reads
    two_step_bytes = (inputs + table               # table write to HBM
                      + n * table                  # broadcast read, n ways
                      + 2 * n * table              # marginal slab write+read
                      + 4 * n)                     # column write
    n_slots = max(1, min(n, max_q.bit_length() - 1))
    vmem_bytes = (inputs + 2 * (n_slots + 1) * table  # slot tables write+read
                  + 4 * n)                            # column write
    rec["delete_pallas"] = {
        "vmem_resident_us": vmem_us,
        "two_step_us": round(two_step_us, 1),
        "speedup_vmem_vs_two_step": round(two_step_us / vmem_us, 2),
        "hbm_bytes": {
            "two_step": two_step_bytes,
            "vmem_resident": vmem_bytes,
            "traffic_ratio": round(two_step_bytes / vmem_bytes, 1),
        },
    }

    # Restricted-W ring column (|E_i| ~ n/k candidates): fused cost must
    # track W, not n — record the fused full-n column for the scaling ratio.
    pids = jnp.asarray(rng.choice(np.arange(1, n), size=w, replace=False)
                       .astype(np.int32))
    rec["restricted"] = {"W": w, "engines": {}}
    for name, impl in (("loop_segment", "segment"), ("fused", "fused"),
                       ("fused_pallas", "fused_pallas")):
        us = col("insert", impl, pids=pids)
        rec["restricted"]["engines"][name] = {
            "sweep_us": round(us, 1),
            "score_evals_per_s": round(w / (us * 1e-6), 1),
        }
    rec["restricted"]["fused_full_n_us"] = rec["engines"]["fused"]["sweep_us"]
    rec["restricted"]["fused_w_cost_fraction_of_full_n"] = round(
        rec["restricted"]["engines"]["fused"]["sweep_us"]
        / rec["engines"]["fused"]["sweep_us"], 3)

    # Compiled-ring per-round sweep: the (W, n) pid_table matrix that the
    # ges_jit/shard_map ring now initializes each round from (every child's
    # W = |E_i| candidates) vs the old full-n (n, n) matrix it used to
    # sweep-then-mask.  Per-round cost must track W, not n; trajectory
    # identity to the full-n path is asserted by tests (test_ges /
    # test_sweeps), this records the cost side.
    from repro.core.partition import pid_table_from_allowed

    allowed = np.zeros((n, n), dtype=bool)
    for y in range(n):
        cand = rng.choice(np.delete(np.arange(n), y), size=w, replace=False)
        allowed[cand, y] = True
    tbl = jnp.asarray(pid_table_from_allowed(allowed))

    def mat(impl, pid_table=None):
        # multi-rep like every other sweep entry: later PRs diff this ratio,
        # and a single sample of a multi-second sweep is scheduler-noise
        return _time(lambda a: sweep(dj, aj, a, kind="insert",
                                     pid_table=pid_table, counts_impl=impl,
                                     **kw), adjj, reps=reps)

    full_us = mat("fused")
    res_us = mat("fused", pid_table=tbl)
    rec["ring_compiled"] = {
        "W": w, "w_over_n": round(w / n, 3),
        "counts_impl": "fused",
        "full_n_round_us": round(full_us, 1),
        "restricted_round_us": round(res_us, 1),
        "w_cost_fraction_of_full_n": round(res_us / full_us, 3),
    }

    # Fusion — the other per-round ring operator: host vs jit through the
    # unified core/fusion.py engine, the pre-refactor full-depth-recompute
    # baseline, and the fusion/sweep cost ratio of one compiled ring round
    # (one pairwise edge union + one (W, n) restricted sweep init).
    rec["fusion"] = bench_fusion(n=n, seed=seed, reps=reps)
    rec["fusion"]["fusion_over_sweep_round"] = round(
        rec["fusion"]["jit_us"]
        / rec["ring_compiled"]["restricted_round_us"], 3)
    return rec


def bench_data_sharded(n: int = 400, m: int = 5000, max_q: int = 256,
                       seed: int = 0, reps: int = 3,
                       shard_counts=(1, 2, 4)) -> dict:
    """Per-round insert-matrix sweep under d-way data-axis sharding at fixed
    GLOBAL m (core/sweeps ``data_shards``: each device contracts m/d rows,
    one psum merges the count tables).

    Two timings per d, because this container is a single CPU core:

    * ``mesh_round_us`` — the real d-(virtual-)device program.  All d shards
      still execute on one core, so this measures correctness-path overhead
      (shard_map + psum), NOT the d-way speedup real hardware gets.
    * ``per_device_round_us`` — a single-device sweep over the ceil(m/d)
      LOCAL rows, everything else fixed: the per-device work the mesh
      distributes, and the honest proxy for d-chip wall time (the psum'd
      (W, Q, R) tables are m-independent and tiny next to the contraction).

    ``per_round_speedup`` = per_device(d=1) / per_device(d), recorded for
    d=4 as the headline ``per_round_speedup_at_d4``.
    """
    from repro.core.sweeps import pad_data_rows, sweep

    rng = np.random.default_rng(seed)
    arities = rng.integers(2, 4, size=n)
    data = np.stack([rng.integers(0, a, size=m) for a in arities], 1)
    r_max = int(arities.max())
    adj = np.zeros((n, n), dtype=np.int8)
    adj[1, 0] = adj[2, 0] = 1
    dj = jnp.asarray(data.astype(np.int32))
    aj = jnp.asarray(arities.astype(np.int32))
    adjj = jnp.asarray(adj)
    kw = dict(kind="insert", ess=10.0, max_q=max_q, r_max=r_max,
              counts_impl="fused")

    rec = {"n": n, "m_global": m, "max_q": max_q, "r_max": r_max,
           "cpu_count": os.cpu_count(),
           "note": ("single-core container: mesh_round_us times the real "
                    "d-virtual-device psum program on one core (overhead "
                    "check); per_device_round_us times the m/d-row local "
                    "contraction each of d real chips would run — the "
                    "honest wall-time proxy at fixed global m"),
           "shards": {}}
    base_us = None
    for d in shard_counts:
        entry = {}
        if d <= len(jax.devices()):
            entry["mesh_round_us"] = round(_time(
                lambda a, _d=d: sweep(dj, aj, a, data_shards=_d, **kw),
                adjj, reps=reps), 1)
        # per-device work: the local shard's rows on ONE device, padded the
        # same way the mesh pads them (sentinel rows are exact no-ops)
        local = np.asarray(pad_data_rows(dj, r_max, d))[: -(-m // d)]
        lj = jnp.asarray(local)
        us = _time(lambda a, _l=lj: sweep(_l, aj, a, **kw), adjj, reps=reps)
        entry["m_local"] = int(local.shape[0])
        entry["per_device_round_us"] = round(us, 1)
        if d == 1:
            base_us = us
        entry["per_round_speedup"] = round(base_us / us, 2)
        rec["shards"][str(d)] = entry
    rec["per_round_speedup_at_d4"] = (
        rec["shards"]["4"]["per_round_speedup"] if "4" in rec["shards"]
        else None)
    return rec


def bench_family_cache(n: int = 120, m: int = 2000, k: int = 4,
                       seed: int = 0) -> dict:
    """Persistent family-score cache (core/score_cache) on an end-to-end
    cGES run: hit rate, score evaluations saved, per-round wall speedup,
    and the trajectory-identity check (cached adj/score must equal the
    uncached run bitwise — the cache's exact-key contract).
    """
    from repro.core import GESConfig, cges

    rng = np.random.default_rng(seed)
    arities = rng.integers(2, 4, size=n).astype(np.int32)
    data = np.stack([rng.integers(0, a, size=m) for a in arities],
                    1).astype(np.int32)
    base = dict(max_q=256, counts_impl="fused")
    r0 = cges(data, arities, k=k, limit=True,
              config=GESConfig(**base, family_cache=False))
    # Capacity sized to the run's working set: the uncached baseline's
    # host-dict ScoreCache is unbounded, so an under-provisioned device
    # cache would charge eviction-induced recomputes to the cache itself.
    r1 = cges(data, arities, k=k, limit=True,
              config=GESConfig(**base, family_cache=True,
                               cache_capacity=8192))
    st = r1.family_cache_stats or {}
    return {
        "n": n, "m": m, "k": k, "engine": "host",
        "hit_rate": round(st.get("hit_rate", 0.0), 4),
        "hits": st.get("hits", 0), "misses": st.get("misses", 0),
        # every hit is one whole column sweep (an O(m) contraction over
        # all candidates of that child) the engine did not run
        "column_sweeps_skipped": st.get("hits", 0),
        "evals_uncached": r0.n_score_evals,
        "evals_cached": r1.n_score_evals,
        "rounds": r1.rounds,
        "uncached_round_s": round(r0.wall_time_s / max(r0.rounds, 1), 3),
        "cached_round_s": round(r1.wall_time_s / max(r1.rounds, 1), 3),
        "per_round_speedup": round(
            (r0.wall_time_s / max(r0.rounds, 1))
            / (r1.wall_time_s / max(r1.rounds, 1)), 2),
        "trajectory_identical": bool(
            np.array_equal(r0.adj, r1.adj) and r0.score == r1.score),
    }


def bench_async_ring(n: int = 12, m: int = 800, k: int = 3,
                     max_rounds: int = 8, seed: int = 7) -> dict:
    """Async double-buffered ring (core/ring_async) vs the lockstep compiled
    ring on one seeded problem, plus the elastic kill-one-member drill.

    Both engines run warm (one throwaway run each eats compilation) on the
    SAME partition, and healthy async replays the lockstep trajectory
    exactly, so the comparison is pure per-round wall time.  Single-core
    honesty, same spirit as bench_data_sharded: the k threaded members
    share this one core, so the measured walls compare the two real
    programs' total per-round cost — the lockstep ring executes every
    member's GES inner loop inside ONE synchronized XLA program per round
    (plus the pmax barrier), while async members run their loops
    independently and receive the predecessor BN into the double-buffered
    mailbox WHILE sweeping.  The per-member phase rows are the k-host
    story: ``permute_wait_us`` is the blocked remainder of neighbor
    transfer (the part NOT hidden behind the sweep) and stays 2-3 orders
    under ``sweep_us``.  ``rounds_executed`` > committed rounds is the
    bounded speculation window — those sweeps are wasted only on one core;
    on k hosts they overlap the verdict lap.
    """
    from repro.core import GESConfig, partition
    from repro.core.ring import RingSpec, ring_cges
    from repro.core.ring_async import run_ring_async_threads
    from repro.data.bn import forward_sample, random_bn
    from repro.launch.mesh import make_host_mesh

    rng = np.random.default_rng(seed)
    bn = random_bn(rng, n=n, n_edges=int(1.3 * n), max_parents=2)
    data = forward_sample(bn, m, rng)
    cfg = GESConfig(max_q=256, counts_impl="fused")
    masks = partition.partition_edges(data, bn.arities, k)
    pid_j = jnp.asarray(partition.pid_tables(masks))

    # lockstep compiled ring, W-wide (the exact engine="jax" program)
    mesh = make_host_mesh(k)
    spec = RingSpec(k=k, max_rounds=max_rounds)
    ring_cges(data, bn.arities, masks, mesh, spec, cfg, pid_tables=pid_j)
    t0 = time.perf_counter()
    _, s_lock, r_lock = ring_cges(data, bn.arities, masks, mesh, spec, cfg,
                                  pid_tables=pid_j)
    lock_wall = time.perf_counter() - t0

    # async threaded ring (same run_member path the process launcher runs)
    kw = dict(config=cfg, max_rounds=max_rounds, wall_limit_s=600.0)
    run_ring_async_threads(data, bn.arities, masks, **kw)
    t0 = time.perf_counter()
    out = run_ring_async_threads(data, bn.arities, masks, **kw)
    async_wall = time.perf_counter() - t0

    surv = out["survivors"]
    r_exec = max(out["members"][i]["rounds_executed"] for i in surv)
    lock_round = lock_wall / max(r_lock, 1) * 1e6
    async_round = async_wall / max(out["rounds"], 1) * 1e6
    tot = {ph: sum(float(np.sum(out["members"][i]["timings"][ph]))
                   for i in surv)
           for ph in ("wait_us", "fuse_us", "sweep_us")}
    per_member = {
        str(i): {
            "permute_wait_us": round(float(np.sum(
                out["members"][i]["timings"]["wait_us"]))
                / out["members"][i]["rounds_executed"], 1),
            "fuse_us": round(float(np.sum(
                out["members"][i]["timings"]["fuse_us"]))
                / out["members"][i]["rounds_executed"], 1),
            "sweep_us": round(float(np.sum(
                out["members"][i]["timings"]["sweep_us"]))
                / out["members"][i]["rounds_executed"], 1),
        }
        for i in surv}

    rec = {
        "n": n, "m": m, "k": k, "max_rounds": max_rounds,
        "counts_impl": cfg.counts_impl, "max_q": cfg.max_q,
        "lockstep": {"round_us": round(lock_round, 1),
                     "rounds": int(r_lock),
                     "best_score": round(float(np.max(s_lock)), 3)},
        "async": {"round_us": round(async_round, 1),
                  "rounds": int(out["rounds"]),
                  "rounds_executed": int(r_exec),
                  "best_score": round(float(out["best_score"]), 3),
                  # blocked transfer wait vs sweep: the overlap evidence
                  "wait_fraction_of_sweep": round(
                      tot["wait_us"] / max(tot["sweep_us"], 1e-9), 4),
                  "phase_us_per_round": per_member},
        "round_speedup_vs_lockstep": round(lock_round / async_round, 2),
        "trajectory_match": bool(
            int(out["rounds"]) == int(r_lock)
            and abs(float(out["best_score"]) - float(np.max(s_lock)))
            <= 1e-2),
    }

    # elastic drill: member 1 goes silent after round 1; survivors fold its
    # E_1 into its ring predecessor, re-stitch, and converge with k-1
    kill = run_ring_async_threads(
        data, bn.arities, masks, config=cfg, max_rounds=max_rounds,
        die_member=1, die_after_round=1, hb_timeout_s=1.5,
        wall_limit_s=600.0)
    rec["elastic"] = {
        "die_member": 1, "die_after_round": 1,
        "survivors": kill["survivors"],
        "rounds": int(kill["rounds"]),
        "best_score": round(float(kill["best_score"]), 3),
        "converged": bool(not kill["timed_out"]
                          and np.isfinite(kill["best_score"])),
        "deaths_via": sorted({d["via"] for i in kill["survivors"]
                              for d in kill["members"][i]["deaths"]}),
    }
    return rec


def _repo_metadata() -> dict:
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=os.path.dirname(
                os.path.abspath(__file__))).stdout.strip() or None
    except OSError:
        commit = None
    return {"platform": jax.default_backend(),
            "jax_version": jax.__version__,
            "device_count": len(jax.devices()),
            "cpu_count": os.cpu_count(),
            "commit": commit,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep-json", default=None,
                    help="also run the fused-vs-loop sweep bench at paper "
                         "scale and write the record to this path")
    ap.add_argument("--sweep-n", type=int, default=400)
    ap.add_argument("--sweep-m", type=int, default=5000)
    args = ap.parse_args()
    for name, us, derived in bench_all():
        print(f"{name},{us:.0f},{derived}")
    if args.sweep_json:
        rec = bench_sweep(n=args.sweep_n, m=args.sweep_m)
        rec["meta"] = _repo_metadata()
        rec["data_sharded"] = bench_data_sharded(n=args.sweep_n,
                                                 m=args.sweep_m)
        rec["family_cache"] = bench_family_cache()
        rec["async_ring"] = bench_async_ring()
        with open(args.sweep_json, "w") as f:
            json.dump(rec, f, indent=2)
            f.write("\n")
        print(f"bdeu_sweep/loop,{rec['engines']['loop_segment']['sweep_us']:.0f},"
              f"n={rec['n']} m={rec['m']}")
        print(f"bdeu_sweep/fused,{rec['engines']['fused']['sweep_us']:.0f},"
              f"speedup={rec['speedup_fused_vs_loop']}x "
              f"dispatch_ratio={rec['dispatch_ratio']}x")
        d = rec["delete"]
        print(f"bdeu_sweep/delete_loop,"
              f"{d['engines']['loop_segment']['sweep_us']:.0f},"
              f"{rec['n']} table builds")
        print(f"bdeu_sweep/delete_fused,{d['engines']['fused']['sweep_us']:.0f},"
              f"speedup={d['speedup_fused_vs_loop']}x (1 table build)")
        dp = rec["delete_pallas"]
        print(f"bdeu_sweep/delete_pallas,{dp['vmem_resident_us']:.0f},"
              f"VMEM-resident column; two_step={dp['two_step_us']:.0f}us "
              f"speedup={dp['speedup_vmem_vs_two_step']}x "
              f"hbm_traffic_ratio={dp['hbm_bytes']['traffic_ratio']}x")
        s = rec["restricted"]
        print(f"bdeu_sweep/restricted_fused,"
              f"{s['engines']['fused']['sweep_us']:.0f},"
              f"W={s['W']} cost={s['fused_w_cost_fraction_of_full_n']}"
              f" of full-n fused")
        r = rec["ring_compiled"]
        print(f"bdeu_sweep/ring_compiled,{r['restricted_round_us']:.0f},"
              f"(W,n) pid_table round W={r['W']} "
              f"cost={r['w_cost_fraction_of_full_n']} of full-n round")
        fu = rec["fusion"]
        print(f"fusion/jit,{fu['jit_us']:.0f},"
              f"host={fu['host_us']:.0f}us "
              f"prerefactor={fu.get('legacy_jit_us', 0):.0f}us "
              f"speedup={fu.get('speedup_jit_vs_prerefactor', 0)}x "
              f"fusion/sweep_round={fu['fusion_over_sweep_round']}")
        ds = rec["data_sharded"]
        print(f"bdeu_sweep/data_sharded,"
              f"{ds['shards']['4']['per_device_round_us']:.0f},"
              f"per-device round at d=4 (m/d rows); "
              f"per_round_speedup_at_d4={ds['per_round_speedup_at_d4']}x "
              f"mesh_d4={ds['shards']['4'].get('mesh_round_us', 0):.0f}us")
        fc = rec["family_cache"]
        print(f"cges/family_cache,{fc['cached_round_s'] * 1e6:.0f},"
              f"hit_rate={fc['hit_rate']} "
              f"column_sweeps_skipped={fc['column_sweeps_skipped']} "
              f"per_round_speedup={fc['per_round_speedup']}x "
              f"identical={fc['trajectory_identical']}")
        ar = rec["async_ring"]
        print(f"ring_async/round,{ar['async']['round_us']:.0f},"
              f"lockstep={ar['lockstep']['round_us']:.0f}us "
              f"speedup={ar['round_speedup_vs_lockstep']}x "
              f"wait/sweep={ar['async']['wait_fraction_of_sweep']} "
              f"match={ar['trajectory_match']} "
              f"elastic_survivors={ar['elastic']['survivors']}")


if __name__ == "__main__":
    main()
