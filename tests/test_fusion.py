"""BN fusion (sigma-consistent edge union) invariants + unified-engine
equivalence: the host and traceable engines in core/fusion.py must agree
adjacency-for-adjacency (same GHO ranks, same lowest-index tie-breaks, same
covered-reversal sequence), and the refactor onto maintained depths /
incremental GHO costs must be output-identical to the pre-refactor code
(pinned hashes + seeded ring trajectories)."""
import hashlib
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core import dag, fusion
# compat imports: pre-unification callers got the traceable engine from ring
from repro.core.ring import fuse_jit, gho_order_jit, sigma_consistent_jit


def _rand(seed, n=7):
    rng = np.random.default_rng(seed)
    return dag.random_dag_np(rng, n, rng.integers(3, 2 * n), max_parents=3)


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_sigma_consistent_is_sigma_dag(seed):
    adj = _rand(seed)
    n = adj.shape[0]
    rng = np.random.default_rng(seed + 1)
    sigma = rng.permutation(n)
    out = fusion.sigma_consistent(adj, sigma)
    rank = np.empty(n, dtype=int)
    rank[sigma] = np.arange(n)
    xs, ys = np.nonzero(out)
    assert np.all(rank[xs] < rank[ys])          # respects sigma => DAG
    assert dag.is_dag_np(out)


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_sigma_consistent_preserves_skeleton(seed):
    """Transform only adds edges / reverses: original skeleton survives."""
    adj = _rand(seed)
    n = adj.shape[0]
    sigma = np.random.default_rng(seed + 1).permutation(n)
    out = fusion.sigma_consistent(adj, sigma)
    sk_in = adj | adj.T
    sk_out = out | out.T
    assert np.all(sk_out[sk_in])                # superset of skeleton


@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_fuse_is_dag_and_contains_skeletons(seed):
    a, b = _rand(seed), _rand(seed + 13)
    f = fusion.fuse([a, b])
    assert dag.is_dag_np(f)
    sk = (a | a.T) | (b | b.T)
    assert np.all((f | f.T)[sk])


def test_fusion_edge_union_empty_cases():
    a = _rand(5)
    zeros = np.zeros_like(a)
    for engine in fusion.FUSION_ENGINES:
        assert np.array_equal(
            fusion.fusion_edge_union(zeros, a, engine=engine), a.astype(bool))
        assert np.array_equal(
            fusion.fusion_edge_union(a, zeros, engine=engine), a.astype(bool))
        assert not fusion.fusion_edge_union(zeros, zeros, engine=engine).any()


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_fuse_jit_matches_invariants(seed):
    """Device-side fusion: result must be a DAG containing both skeletons."""
    a, b = _rand(seed), _rand(seed + 29)
    f = np.asarray(fuse_jit(jnp.asarray(a.astype(np.int8)),
                            jnp.asarray(b.astype(np.int8))))
    assert dag.is_dag_np(f.astype(bool))
    sk = (a | a.T) | (b | b.T)
    assert np.all((f.astype(bool) | f.astype(bool).T)[sk])


def test_gho_order_jit_is_permutation():
    a, b = _rand(3), _rand(4)
    rank = np.asarray(gho_order_jit(jnp.asarray(a.astype(np.int8)),
                                    jnp.asarray(b.astype(np.int8))))
    assert sorted(rank.tolist()) == list(range(a.shape[0]))


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_sigma_consistent_jit_matches_host(seed):
    adj = _rand(seed)
    n = adj.shape[0]
    sigma = np.random.default_rng(seed + 1).permutation(n)
    rank = np.empty(n, dtype=np.int32)
    rank[sigma] = np.arange(n)
    host = fusion.sigma_consistent(adj, sigma)
    dev = np.asarray(sigma_consistent_jit(
        jnp.asarray(adj.astype(np.int8)), jnp.asarray(rank)))
    assert np.array_equal(host, dev.astype(bool))


# ---------------------------------------------------------------------------
# Unified-engine equivalence (tentpole): host == jit, adjacency-for-adjacency
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000))
@settings(max_examples=12, deadline=None)
def test_fuse_host_vs_jit_engines(seed):
    """fuse(engine="jit") must equal fuse(engine="host") exactly, on mixed
    sizes and input counts — including all-empty and one-empty stacks."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 14))
    j = int(rng.integers(2, 4))
    adjs = [dag.random_dag_np(rng, n, int(rng.integers(0, 2 * n)),
                              max_parents=3) for _ in range(j)]
    if seed % 3 == 1:
        adjs[0] = np.zeros_like(adjs[0])        # one empty input
    if seed % 5 == 2:
        adjs = [np.zeros_like(a) for a in adjs]  # all empty
    f_host = fusion.fuse(adjs, engine="host")
    f_jit = fusion.fuse(adjs, engine="jit")
    assert np.array_equal(f_host, f_jit)
    # pairwise path (the ring's operator) with the Algorithm-1 empty guard
    f_eu_h = fusion.fusion_edge_union(adjs[0], adjs[1], engine="host")
    f_eu_j = fusion.fusion_edge_union(adjs[0], adjs[1], engine="jit")
    assert np.array_equal(f_eu_h, f_eu_j)
    f_tr = np.asarray(fusion.fuse_trace(jnp.asarray(adjs[0].astype(np.int8)),
                                        jnp.asarray(adjs[1].astype(np.int8))))
    assert np.array_equal(f_eu_h, f_tr.astype(bool))


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_fuse_fixed_sigma_host_vs_jit(seed):
    """Engine equality also under a caller-supplied ordering."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 12))
    adjs = [dag.random_dag_np(rng, n, int(rng.integers(1, 2 * n)),
                              max_parents=3) for _ in range(2)]
    sigma = rng.permutation(n)
    assert np.array_equal(fusion.fuse(adjs, sigma=sigma, engine="host"),
                          fusion.fuse(adjs, sigma=sigma, engine="jit"))


def test_fuse_pinned_outputs():
    """The maintained-depth / incremental-cost engines are output-identical
    to the pre-refactor implementation: hashes captured from the PR 3 code
    on seeded random DAG stacks."""
    pins = [
        ((0, 6, 2),
         "25f38ab0f0ca2152e789795b58f7464e5da7350aa5ccaa581efeaf80cf8abbca"),
        ((1, 9, 2),
         "694bcb293cadaad165a4ce2248d979c3e91fde84c7b559232fc8966a3758a007"),
        ((2, 13, 2),
         "68fd0ad275fca2a42b53cbd2c2c024986ad9365582cd12b6875ade0d9cd51f44"),
        ((4, 11, 3),
         "c658e59d58581342b96e941bd4cbe65f5d862b014876f3ff87685e2c536e0147"),
    ]
    for (seed, n, j), want in pins:
        rng = np.random.default_rng(seed)
        adjs = [dag.random_dag_np(rng, n, rng.integers(n // 2, 2 * n),
                                  max_parents=3) for _ in range(j)]
        for engine in fusion.FUSION_ENGINES:
            f = fusion.fuse(adjs, engine=engine)
            got = hashlib.sha256(
                np.ascontiguousarray(f.astype(np.uint8)).tobytes()).hexdigest()
            assert got == want, (engine, seed, n, j)


def test_gho_order_incremental_identity():
    """The incremental cost update (subtract the sunk node's stacked column)
    reproduces the re-summing implementation order-for-order — including tie
    cases, which must break to the lowest node index."""

    def gho_resum(adjs):                 # pre-refactor reference, re-sums
        n = adjs[0].shape[0]             # all k (n, n) masks per position
        remaining = np.ones(n, dtype=bool)
        order = np.empty(n, dtype=np.int64)
        stack = [a.astype(bool) for a in adjs]
        for pos in range(n - 1, -1, -1):
            costs = np.full(n, np.inf)
            idx = np.flatnonzero(remaining)
            sub_cost = np.zeros(n, dtype=np.int64)
            for a in stack:
                sub_cost += (a & remaining[None, :]).sum(axis=1)
            costs[idx] = sub_cost[idx]
            v = int(np.argmin(costs))
            order[pos] = v
            remaining[v] = False
        return order

    n = 9
    zeros = np.zeros((n, n), dtype=bool)
    chain = np.zeros((n, n), dtype=bool)
    for i in range(n - 1):
        chain[i, i + 1] = True
    cases = [
        [zeros, zeros],                       # total tie: lowest index wins
        [chain, chain],                       # duplicated input
        [chain, chain.T.copy()],              # symmetric costs => ties
    ]
    for seed in range(6):
        r = np.random.default_rng(seed)
        m = int(r.integers(2, 14))
        cases.append([dag.random_dag_np(r, m, int(r.integers(0, 2 * m)),
                                        max_parents=3)
                      for _ in range(int(r.integers(1, 4)))])
    for adjs in cases:
        want = gho_resum(adjs)
        got = fusion.gho_order(adjs)
        assert np.array_equal(got, want), (len(adjs), adjs[0].shape)
        # jit rank is the inverse permutation of the same order
        rank = np.asarray(fusion.gho_rank_trace(
            jnp.asarray(np.stack(adjs).astype(np.int8))))
        assert np.array_equal(rank[want], np.arange(adjs[0].shape[0]))
    assert np.array_equal(fusion.gho_order([zeros, zeros]),
                          np.arange(n)[::-1])  # explicit tie-break pin


def test_depth_maintenance_matches_scratch_oracle():
    """The maintained depth vector equals the from-scratch longest-path
    layer at every subgraph size (the invariant the transforms rely on)."""
    rng = np.random.default_rng(23)
    adj = dag.random_dag_np(rng, 10, 18, max_parents=3)
    in_s = np.ones(10, dtype=bool)
    depth = fusion._settle_depth_np(adj, in_s, np.zeros(10, dtype=np.int64))
    assert np.array_equal(depth, fusion._subgraph_depth(adj, in_s))
    for v in rng.permutation(10)[:6]:
        # drop sinks the way sigma_consistent does: recompute oracle fresh
        in_s[v] = False
        depth = fusion._settle_depth_np(adj, in_s,
                                        np.where(in_s, depth, -1))
        assert np.array_equal(depth, fusion._subgraph_depth(adj, in_s))


# ---------------------------------------------------------------------------
# Engine knob plumbing (REPRO_FUSION_ENGINE / fusion_engine=)
# ---------------------------------------------------------------------------

def test_fusion_engine_validation(monkeypatch):
    with pytest.raises(ValueError, match="unknown fusion engine"):
        fusion.check_fusion_engine("bogus")
    with pytest.raises(ValueError, match="unknown fusion engine"):
        fusion.fuse([_rand(0), _rand(1)], engine="numpy")
    monkeypatch.setenv("REPRO_FUSION_ENGINE", "jti")   # typo'd env fails loud
    with pytest.raises(ValueError, match="unknown fusion engine"):
        fusion.resolve_fusion_engine(None)
    monkeypatch.setenv("REPRO_FUSION_ENGINE", "jit")
    assert fusion.resolve_fusion_engine(None) == "jit"
    monkeypatch.delenv("REPRO_FUSION_ENGINE", raising=False)
    assert fusion.resolve_fusion_engine(None) == "host"
    assert fusion.resolve_fusion_engine("host") == "host"


def test_cges_fusion_engine_knob(monkeypatch):
    """cges() resolves fusion_engine from the env (mirroring
    REPRO_COUNTS_IMPL), errors loudly on unknown values BEFORE learning, and
    both engines drive the host round loop to the same adjacency."""
    from repro.core import GESConfig
    from repro.core.cges import cges
    from repro.data.bn import forward_sample, random_bn

    rng = np.random.default_rng(6)
    bn = random_bn(rng, n=7, n_edges=8, max_parents=2)
    data = forward_sample(bn, 250, rng)
    cfg = GESConfig(max_q=64)

    monkeypatch.setenv("REPRO_FUSION_ENGINE", "wat")
    with pytest.raises(ValueError, match="unknown fusion engine"):
        cges(data, bn.arities, k=2, config=cfg, max_rounds=1)
    monkeypatch.delenv("REPRO_FUSION_ENGINE")
    with pytest.raises(ValueError, match="unknown fusion engine"):
        cges(data, bn.arities, k=2, config=cfg, max_rounds=1,
             fusion_engine="trace")

    res = {eng: cges(data, bn.arities, k=2, config=cfg, max_rounds=3,
                     fusion_engine=eng) for eng in fusion.FUSION_ENGINES}
    assert np.array_equal(res["host"].adj, res["jit"].adj)
    assert np.isclose(res["host"].score, res["jit"].score, rtol=1e-9)
    assert res["host"].rounds == res["jit"].rounds


# ---------------------------------------------------------------------------
# Ring-trajectory regression across the refactor
# ---------------------------------------------------------------------------

def test_ring_cges_trajectory_pinned():
    """Seeded ring_cges trajectories on k in {1, 2} meshes are pinned to
    the exact-arithmetic oracle (tests/_exact_ges.py: float64 libm scores,
    lowest-index ties, canonical direction for score-equivalent
    insertions): same best graphs, same scores, same rounds — and the
    round/edge counts that oracle gives for this seed.  Subprocess: needs a
    multi-device host platform."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import sys
        sys.path[:0] = ["src", "tests"]
        import numpy as np, jax
        from jax.sharding import Mesh
        from _exact_ges import exact_ring
        from repro.core import GESConfig, partition
        from repro.core.cges import edge_add_limit
        from repro.core.ring import RingSpec, ring_cges
        from repro.data.bn import forward_sample, random_bn

        PINS = {1: (2, 22), 2: (3, 47)}   # k -> (rounds, edges) of the oracle
        rng = np.random.default_rng(3)
        bn = random_bn(rng, n=12, n_edges=16, max_parents=2)
        data = forward_sample(bn, 600, rng)
        for k, (want_rounds, want_edges) in PINS.items():
            masks = partition.partition_edges(data, bn.arities, k)
            mesh = Mesh(np.array(jax.devices()[:k]), ("ring",))
            spec = RingSpec(k=k, max_rounds=6)
            cfg = GESConfig(max_q=64, counts_impl="segment")
            lim = edge_add_limit(bn.n, k)
            g, s, r = ring_cges(data, bn.arities, masks, mesh, spec, cfg,
                                add_limit=lim)
            gE, sE, rE = exact_ring(data, bn.arities, masks, lim, max_q=64,
                                    max_rounds=6)
            assert (rE, int(gE.sum())) == (want_rounds, want_edges), (k, rE)
            assert r == rE, (k, r)
            assert np.array_equal(g, gE), (k, "vs exact oracle")
            assert np.allclose(s, sE, rtol=1e-5), (k, s, sE)
        print("RING_PINNED_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "RING_PINNED_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# Paper-scale benchmark (slow: deselected in CI)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fusion_bench_n400():
    """The n=400 jit fusion step must beat the pre-refactor
    per-reversal-depth-recompute baseline (benchmarks/kernel_bench.py
    ``bench_fusion``, CPU timings)."""
    bench_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        from kernel_bench import bench_fusion
    finally:
        sys.path.remove(bench_dir)
    rec = bench_fusion(n=400, reps=1)
    assert rec["speedup_jit_vs_prerefactor"] > 1.0, rec
