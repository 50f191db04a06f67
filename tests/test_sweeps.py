"""Unified sweep engine (core/sweeps): fused BES delete-by-marginalization
equality vs the loop engine (mixed arities, padded r_max, empty parent set,
max_q guard), restricted-W columns, masked-convention regressions, and ring
trajectory invariance across counts_impls."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bdeu, sweeps
from repro.core.sweeps import sweep
from repro.data.bn import forward_sample, random_bn

FUSED_IMPLS = ["fused", "fused_pallas"]


@pytest.fixture(scope="module")
def mixed_case():
    """Mixed arities with most columns below r_max (dense-padding exercised)."""
    rng = np.random.default_rng(5)
    arities = np.array([2, 3, 4, 2, 3, 2, 4, 2, 3, 2], dtype=np.int64)
    n = arities.size
    data = np.stack([rng.integers(0, a, size=900) for a in arities], 1)
    return data.astype(np.int64), arities


def _jnp(data, arities):
    return (jnp.asarray(data.astype(np.int32)),
            jnp.asarray(arities.astype(np.int32)))


def _delete_col(data, arities, adj, y, impl, max_q=256, pids=None):
    dj, aj = _jnp(data, arities)
    return np.asarray(sweep(
        dj, aj, jnp.asarray(adj), kind="delete", y=y, pids=pids, ess=10.0,
        max_q=max_q, r_max=int(arities.max()), counts_impl=impl))


# ---------------------------------------------------------------------------
# Fused BES delete: one family-table build, marginalized per parent slot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_delete_column_matches_host_oracle(mixed_case, impl):
    """Fused delete deltas == exact host-oracle deltas at every parent."""
    data, arities = mixed_case
    n = arities.size
    adj = np.zeros((n, n), dtype=np.int8)
    pa = [1, 2, 6]                       # arities 3, 4, 4 -> q0 = 48
    adj[pa, 0] = 1
    col = _delete_col(data, arities, adj, 0, impl)
    base = bdeu.local_score_np(data, arities, 0, pa)
    for x in range(n):
        if adj[x, 0]:
            want = bdeu.local_score_np(
                data, arities, 0, [p for p in pa if p != x]) - base
            assert np.isclose(col[x], want, rtol=2e-5, atol=1e-3), x
        else:
            assert np.isneginf(col[x])   # illegal toggle, engine-masked


@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_delete_column_matches_loop_engine(mixed_case, impl):
    """Fused == loop delete column entry-for-entry (both engine-masked)."""
    data, arities = mixed_case
    n = arities.size
    adj = np.zeros((n, n), dtype=np.int8)
    adj[[0, 4, 8], 3] = 1
    adj[[2, 5], 7] = 1
    for y in (3, 7):
        col_loop = _delete_col(data, arities, adj, y, "segment")
        col_fus = _delete_col(data, arities, adj, y, impl)
        assert np.array_equal(np.isneginf(col_loop), np.isneginf(col_fus))
        f = np.isfinite(col_loop)
        assert np.allclose(col_loop[f], col_fus[f], rtol=1e-4, atol=2e-3)


def test_delete_column_empty_parent_set(mixed_case):
    """With Pa_y empty every delete is illegal: whole column -inf, no NaNs,
    identical under every backend."""
    data, arities = mixed_case
    n = arities.size
    adj = np.zeros((n, n), dtype=np.int8)
    for impl in ["segment"] + FUSED_IMPLS:
        col = _delete_col(data, arities, adj, 2, impl)
        assert np.all(np.isneginf(col)), impl
        assert not np.isnan(col).any(), impl


def test_delete_column_max_q_guard(mixed_case):
    """Candidates whose REDUCED family still overflows max_q are -inf with
    the loop engine's exact guard convention; deletes that fit are finite."""
    data, arities = mixed_case
    n = arities.size
    adj = np.zeros((n, n), dtype=np.int8)
    pa = [1, 2, 6]                        # q0 = 3*4*4 = 48
    adj[pa, 0] = 1
    # max_q = 24: the family itself overflows; removing x=1 leaves q=16 (ok),
    # removing x=2 or x=6 leaves q=12 (ok) -> deltas vs the -inf base are
    # +inf under BOTH engines (identical trajectory decisions), and the
    # engines' +/-inf patterns must agree entry-for-entry.
    col_loop = _delete_col(data, arities, adj, 0, "segment", max_q=24)
    col_fus = _delete_col(data, arities, adj, 0, "fused", max_q=24)
    assert np.array_equal(np.isposinf(col_loop), np.isposinf(col_fus))
    assert np.array_equal(np.isneginf(col_loop), np.isneginf(col_fus))
    assert np.isposinf(col_loop[np.asarray(pa)]).all()
    # max_q = 12: removing one arity-4 parent leaves q=12 (fits: +inf delta
    # vs the -inf base) but removing the arity-3 parent leaves q=16 -> the
    # REDUCED family is guarded -inf too, and -inf - (-inf) = NaN under both
    # engines — the guard conventions must agree entry-for-entry.
    col_loop = _delete_col(data, arities, adj, 0, "segment", max_q=12)
    col_fus = _delete_col(data, arities, adj, 0, "fused", max_q=12)
    assert np.array_equal(np.isposinf(col_loop), np.isposinf(col_fus))
    assert np.array_equal(np.isnan(col_loop), np.isnan(col_fus))
    assert np.isnan(col_loop[1]) and np.isnan(col_fus[1])
    assert np.isposinf(col_fus[2]) and np.isposinf(col_fus[6])


@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_delete_matrix_matches_loop_engine(mixed_case, impl):
    """Full (n, n) BES delta matrix through the unified engine: fused ==
    loop everywhere (the ges_jit BES initialization path)."""
    data, arities = mixed_case
    n = arities.size
    rng = np.random.default_rng(1)
    adj = np.zeros((n, n), dtype=np.int8)
    for y in range(n):
        for x in rng.choice(n, size=2, replace=False):
            if x != y:
                adj[x, y] = 1
    dj, aj = _jnp(data, arities)
    kw = dict(kind="delete", ess=10.0, max_q=256, r_max=int(arities.max()))
    D_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                              counts_impl="segment", **kw))
    D_fus = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                             counts_impl=impl, **kw))
    assert np.array_equal(np.isneginf(D_loop), np.isneginf(D_fus))
    f = np.isfinite(D_loop)
    assert np.allclose(D_loop[f], D_fus[f], rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# Restricted-W columns (ring E_i subsets)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["insert", "delete"])
@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_restricted_column_matches_loop(mixed_case, kind, impl):
    """(W,) restricted columns agree with the loop engine entry-for-entry,
    including illegal pids (self-pads, wrong edge state) masked to -inf."""
    data, arities = mixed_case
    n = arities.size
    adj = np.zeros((n, n), dtype=np.int8)
    adj[[1, 4], 0] = 1
    y = 0
    pids = jnp.asarray(np.array([1, 3, 4, 7, 9, y, y], dtype=np.int32))
    dj, aj = _jnp(data, arities)
    kw = dict(kind=kind, y=y, pids=pids, ess=10.0, max_q=256,
              r_max=int(arities.max()))
    col_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                                counts_impl="segment", **kw))
    col_fus = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                               counts_impl=impl, **kw))
    assert col_fus.shape == (7,)
    assert np.array_equal(np.isneginf(col_loop), np.isneginf(col_fus))
    f = np.isfinite(col_loop)
    assert f.any()
    assert np.allclose(col_loop[f], col_fus[f], rtol=1e-4, atol=2e-3)


def test_restricted_kernel_contracts_w_columns_not_n(mixed_case):
    """The restricted Pallas variant's counts slab is (r_max, r_max, max_q,
    W): the contraction width — and hence fused cost — scales with the
    candidate subset W, not the full n."""
    from repro.kernels.bdeu_sweep import sweep_counts, sweep_counts_restricted

    data, arities = mixed_case
    dj, aj = _jnp(data, arities)
    r_max = int(arities.max())
    m, n = data.shape
    cfg = jnp.zeros((m,), jnp.int32)
    child = dj[:, 0]
    pids = jnp.asarray(np.array([1, 3, 4], dtype=np.int32))
    full = sweep_counts(cfg, child, dj, max_q=32, r_max=r_max)
    sub = sweep_counts_restricted(cfg, child, dj, pids, max_q=32, r_max=r_max)
    assert full.shape == (r_max, r_max, 32, n)
    assert sub.shape == (r_max, r_max, 32, 3)
    # gathered-before-contraction == gathered-after-contraction
    want = np.asarray(full)[..., np.asarray(pids)]
    assert np.array_equal(np.asarray(sub), want)


def test_sweep_matrix_rejects_pids():
    data = np.zeros((4, 3), dtype=np.int64)
    ar = np.full(3, 2)
    dj, aj = _jnp(data, ar)
    with pytest.raises(ValueError):
        sweep(dj, aj, jnp.zeros((3, 3), jnp.int8), kind="insert",
              pids=jnp.arange(2), ess=10.0, max_q=8, r_max=2)
    with pytest.raises(ValueError):
        sweep(dj, aj, jnp.zeros((3, 3), jnp.int8), kind="reverse",
              ess=10.0, max_q=8, r_max=2)


def test_sweep_rejects_bad_candidate_ids():
    """Over-long or out-of-range pids/pid_table raise a clear ValueError
    instead of flowing into the gather as silent wrong shapes."""
    n = 5
    data = np.zeros((8, n), dtype=np.int64)
    ar = np.full(n, 2)
    dj, aj = _jnp(data, ar)
    adj = jnp.zeros((n, n), jnp.int8)
    kw = dict(ess=10.0, max_q=8, r_max=2)
    with pytest.raises(ValueError, match="candidates per column"):
        sweep(dj, aj, adj, kind="insert", y=0,
              pids=jnp.zeros(n + 1, jnp.int32), **kw)
    with pytest.raises(ValueError, match="out-of-range"):
        sweep(dj, aj, adj, kind="insert", y=0,
              pids=jnp.asarray([0, n], dtype=jnp.int32), **kw)
    with pytest.raises(ValueError, match="out-of-range"):
        sweep(dj, aj, adj, kind="insert", y=0,
              pids=jnp.asarray([-1, 1], dtype=jnp.int32), **kw)
    with pytest.raises(ValueError, match="integer"):
        sweep(dj, aj, adj, kind="insert", y=0,
              pids=jnp.asarray([0.0, 1.0]), **kw)
    with pytest.raises(ValueError, match="candidates per column"):
        sweep(dj, aj, adj, kind="insert",
              pid_table=jnp.zeros((n, n + 2), jnp.int32), **kw)
    with pytest.raises(ValueError, match="out-of-range"):
        sweep(dj, aj, adj, kind="insert",
              pid_table=jnp.full((n, 2), n, dtype=jnp.int32), **kw)
    with pytest.raises(ValueError, match=r"\(n, W\)"):
        sweep(dj, aj, adj, kind="insert",
              pid_table=jnp.zeros((n - 1, 2), jnp.int32), **kw)
    with pytest.raises(ValueError, match="not both"):
        sweep(dj, aj, adj, kind="insert", y=0,
              pid_table=jnp.zeros((n, 2), jnp.int32), **kw)


def test_unknown_counts_impl_fails_loudly():
    """A typo'd backend (config or REPRO_COUNTS_IMPL) must raise, not
    silently fall through the dispatch chains to 'segment'."""
    from repro.core import GESConfig

    with pytest.raises(ValueError, match="unknown counts_impl"):
        GESConfig(counts_impl="fuesd")
    data = np.zeros((4, 3), dtype=np.int64)
    ar = np.full(3, 2)
    dj, aj = _jnp(data, ar)
    with pytest.raises(ValueError, match="unknown counts_impl"):
        sweep(dj, aj, jnp.zeros((3, 3), jnp.int8), kind="insert",
              counts_impl="Fused", ess=10.0, max_q=8, r_max=2)


# ---------------------------------------------------------------------------
# Restricted (W, n) matrix sweeps (the compiled ring's per-round rescoring)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["insert", "delete"])
@pytest.mark.parametrize("impl", ["segment"] + FUSED_IMPLS)
def test_restricted_matrix_matches_full(mixed_case, kind, impl):
    """sweep(pid_table=...) returns the (W, n) matrix whose entry [w, y]
    equals the full (n, n) loop matrix at [pid_table[y, w], y], with
    self-pads -inf — under every backend."""
    from repro.core.partition import pid_table_from_allowed

    data, arities = mixed_case
    n = arities.size
    rng = np.random.default_rng(7)
    adj = np.zeros((n, n), dtype=np.int8)
    adj[[1, 4], 0] = 1
    adj[[0, 2, 6], 5] = 1
    allowed = rng.random((n, n)) < 0.4
    allowed[:, 8] = False                 # empty E_i column: all self-pads
    np.fill_diagonal(allowed, False)
    tbl = pid_table_from_allowed(allowed)
    W = tbl.shape[1]
    dj, aj = _jnp(data, arities)
    kw = dict(kind=kind, ess=10.0, max_q=256, r_max=int(arities.max()))
    D_full = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                              counts_impl="segment", **kw))
    D_res = np.asarray(sweep(dj, aj, jnp.asarray(adj), counts_impl=impl,
                             pid_table=jnp.asarray(tbl), **kw))
    assert D_res.shape == (W, n)
    assert np.all(np.isneginf(D_res[:, 8]))
    for y in range(n):
        for w in range(W):
            x = tbl[y, w]
            if x == y:
                assert np.isneginf(D_res[w, y])
            elif np.isfinite(D_full[x, y]):
                assert np.isclose(D_res[w, y], D_full[x, y],
                                  rtol=1e-4, atol=2e-3), (y, w)
            else:
                assert np.isneginf(D_res[w, y]) == np.isneginf(D_full[x, y])


@pytest.mark.parametrize("kind", ["insert", "delete"])
@pytest.mark.parametrize("impl", ["segment", "fused"])
def test_restricted_matrix_bitwise_equals_full(mixed_case, kind, impl):
    """Restricted entries are BITWISE equal to the full-n matrix (same
    engine): the compiled ring's full-n tie-breaking argmax
    (ges._masked_argmax_mapped) relies on exact value equality between the
    (W, n) and (n, n) programs — 1-ulp drift would let score-equivalent
    ties (x->y vs y->x) resolve differently."""
    from repro.core.partition import pid_table_from_allowed

    data, arities = mixed_case
    n = arities.size
    rng = np.random.default_rng(13)
    allowed = rng.random((n, n)) < 0.5
    np.fill_diagonal(allowed, False)
    # parents drawn inside `allowed` so delete entries are plentiful
    adj = np.zeros((n, n), dtype=np.int8)
    for y in range(n):
        cand = np.flatnonzero(allowed[:, y])
        for x in cand[:2]:
            adj[x, y] = 1
    tbl = pid_table_from_allowed(allowed)
    dj, aj = _jnp(data, arities)
    kw = dict(kind=kind, ess=10.0, max_q=256, r_max=int(arities.max()))
    D_full = np.asarray(sweep(dj, aj, jnp.asarray(adj), counts_impl=impl,
                              **kw))
    D_res = np.asarray(sweep(dj, aj, jnp.asarray(adj), counts_impl=impl,
                             pid_table=jnp.asarray(tbl), **kw))
    checked = 0
    for y in range(n):
        for w in range(tbl.shape[1]):
            x = tbl[y, w]
            if x == y:
                continue
            a, b = D_res[w, y], D_full[x, y]
            if np.isfinite(b) or np.isfinite(a):
                assert a == b, (y, w, x, a, b)    # bitwise, not isclose
                checked += 1
    assert checked > 10


# ---------------------------------------------------------------------------
# End-to-end trajectory invariance
# ---------------------------------------------------------------------------

def test_ges_host_bes_trajectory_identity(mixed_case):
    """A BES-heavy host run (dense init graph) takes the identical greedy
    delete trajectory under the loop and fused engines."""
    from repro.core import GESConfig, ges_host
    from repro.core.dag import is_dag_np

    data, arities = mixed_case
    n = arities.size
    rng = np.random.default_rng(3)
    init = np.zeros((n, n), dtype=np.int8)
    for y in range(1, n):                 # DAG: parents only from lower ids
        for x in rng.choice(y, size=min(2, y), replace=False):
            init[x, y] = 1
    res = {}
    for impl in ("segment", "fused", "fused_pallas"):
        res[impl] = ges_host(data, arities, init_adj=init,
                             config=GESConfig(max_q=256, counts_impl=impl),
                             phases="bes")
    assert res["segment"].n_deletes > 0    # the BES phase actually ran
    for impl in FUSED_IMPLS:
        assert np.array_equal(res[impl].adj, res["segment"].adj)
        assert np.isclose(res[impl].score, res["segment"].score, rtol=1e-9)
    assert is_dag_np(res["segment"].adj)


def test_ring_cges_trajectory_invariance():
    """The compiled W-wide ring (pid_table threaded through the shard_map
    while_loop) is trajectory-identical to (a) the old full-n-masked
    compiled path, (b) every other counts_impl backend, and (c) the
    host-engine cGES driver (ges_host + fusion_edge_union round loop), on
    k in {1, 2} meshes (subprocess: needs a multi-device host platform)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import sys
        sys.path.insert(0, "src")
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import GESConfig, fusion, ges_host, partition
        from repro.core.ring import RingSpec, ring_cges
        from repro.data.bn import forward_sample, random_bn

        rng = np.random.default_rng(2)
        bn = random_bn(rng, n=8, n_edges=9, max_parents=2)
        data = forward_sample(bn, 400, rng)
        n = bn.n
        MAX_ROUNDS = 3

        def host_ring(masks, k, cfg):
            '''Host-engine mirror of _ring_body: ges_host processes, the
            same one-hop fusion and convergence rule, keeping the graphs
            of the last globally-improving round (Algorithm 1 best BNs).'''
            graphs = [np.zeros((n, n), np.int8) for _ in range(k)]
            best_g, best_s = list(graphs), [-np.inf] * k
            best, go, rnd = -np.inf, True, 0
            while go and rnd < MAX_ROUNDS:
                preds = [graphs[(i - 1) % k] for i in range(k)]
                new_g, new_s = [], []
                for i in range(k):
                    init = fusion.fusion_edge_union(
                        graphs[i], preds[i]).astype(np.int8)
                    res = ges_host(data, bn.arities, init_adj=init,
                                   allowed=masks[i], config=cfg)
                    new_g.append(res.adj); new_s.append(res.score)
                graphs, rnd = new_g, rnd + 1
                round_best = max(new_s)
                go = round_best > best + cfg.tol
                if go:
                    best_g, best_s = new_g, new_s
                best = max(best, round_best)
            return np.stack(best_g), np.array(best_s), rnd

        for k in (1, 2):
            masks = partition.partition_edges(data, bn.arities, k)
            mesh = Mesh(np.array(jax.devices()[:k]), ("ring",))
            spec = RingSpec(k=k, max_rounds=MAX_ROUNDS)
            impls = (("segment", "fused", "fused_pallas") if k == 2
                     else ("segment", "fused"))
            out = {}
            for impl in impls:
                cfg = GESConfig(max_q=64, counts_impl=impl)
                gW, sW, rW = ring_cges(data, bn.arities, masks, mesh,
                                       spec, cfg, restricted=True)
                gF, sF, rF = ring_cges(data, bn.arities, masks, mesh,
                                       spec, cfg, restricted=False)
                assert np.array_equal(gW, gF), (k, impl, "W vs full-n")
                assert np.allclose(sW, sF, rtol=1e-6), (k, impl)
                assert rW == rF, (k, impl)
                out[impl] = (gW, sW)
            for impl in impls[1:]:
                assert np.array_equal(out[impls[0]][0], out[impl][0]), \\
                    (k, impl, "impl mismatch")
                assert np.allclose(out[impls[0]][1], out[impl][1],
                                   rtol=1e-6)
            gH, sH, rH = host_ring(masks, k,
                                   GESConfig(max_q=64,
                                             counts_impl="segment"))
            assert np.array_equal(out["segment"][0], gH), (k, "vs host")
            assert np.allclose(out["segment"][1], sH,
                               rtol=1e-5, atol=1e-2), (k, "host scores")
            assert out["segment"][0].any()   # the ring actually learned
        print("RING_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "RING_OK" in r.stdout, r.stderr[-3000:]


def test_ring_cges_trajectory_k3_k4():
    """ppermute neighbor wiring on non-trivial cycles: k in {3, 4} rings
    (odd and larger-even), pinned against the host-engine oracle, with the
    restricted W-wide pid_table path and the persistent family cache each
    exercised on the multi-hop cycle (subprocess: forced host devices).

    max_q=512 keeps every fused-init family (largest q = 324 for these
    seeds) under the compiled-table guard: when the guard bites a base
    family but not its reduced families, host and compiled BES
    legitimately diverge (see bdeu.graph_score_jax) and the cross-engine
    pin would be vacuous."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import sys
        sys.path.insert(0, "src")
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import GESConfig, fusion, ges_host, partition
        from repro.core.ring import RingSpec, ring_cges
        from repro.data.bn import forward_sample, random_bn

        rng = np.random.default_rng(5)
        bn = random_bn(rng, n=9, n_edges=11, max_parents=2)
        data = forward_sample(bn, 400, rng)
        n = bn.n
        MAX_ROUNDS = 3

        def host_ring(masks, k, cfg):
            graphs = [np.zeros((n, n), np.int8) for _ in range(k)]
            best_g, best_s = list(graphs), [-np.inf] * k
            best, go, rnd = -np.inf, True, 0
            while go and rnd < MAX_ROUNDS:
                preds = [graphs[(i - 1) % k] for i in range(k)]
                new_g, new_s = [], []
                for i in range(k):
                    init = fusion.fusion_edge_union(
                        graphs[i], preds[i]).astype(np.int8)
                    res = ges_host(data, bn.arities, init_adj=init,
                                   allowed=masks[i], config=cfg)
                    new_g.append(res.adj); new_s.append(res.score)
                graphs, rnd = new_g, rnd + 1
                round_best = max(new_s)
                go = round_best > best + cfg.tol
                if go:
                    best_g, best_s = new_g, new_s
                best = max(best, round_best)
            return np.stack(best_g), np.array(best_s), rnd

        for k, impl in ((3, "segment"), (4, "fused")):
            masks = partition.partition_edges(data, bn.arities, k)
            mesh = Mesh(np.array(jax.devices()[:k]), ("ring",))
            spec = RingSpec(k=k, max_rounds=MAX_ROUNDS)
            cfg = GESConfig(max_q=512, counts_impl=impl)
            # restricted (W-wide pid_table) vs full-n on the k-cycle
            gW, sW, rW = ring_cges(data, bn.arities, masks, mesh,
                                   spec, cfg, restricted=True)
            gF, sF, rF = ring_cges(data, bn.arities, masks, mesh,
                                   spec, cfg, restricted=False)
            assert np.array_equal(gW, gF), (k, "W vs full-n")
            assert np.allclose(sW, sF, rtol=1e-6), (k,)
            assert rW == rF, (k,)
            # persistent family cache on the multi-hop cycle: bitwise pin
            cfg_fc = GESConfig(max_q=512, counts_impl=impl,
                               family_cache=True)
            gC, sC, rC, stats = ring_cges(data, bn.arities, masks, mesh,
                                          spec, cfg_fc, restricted=True,
                                          return_cache_stats=True)
            assert np.array_equal(gC, gW), (k, "family-cache drift")
            assert np.allclose(sC, sW, rtol=1e-6), (k,)
            assert rC == rW, (k,)
            # host-engine oracle: the cycle's one-hop information flow
            gH, sH, rH = host_ring(masks, k,
                                   GESConfig(max_q=512,
                                             counts_impl="segment"))
            assert np.array_equal(gW, gH), (k, "vs host oracle")
            assert np.allclose(sW, sH, rtol=1e-5, atol=1e-2), (k,)
            assert rW == rH, (k,)
            assert gW.any()
        print("RING_K34_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "RING_K34_OK" in r.stdout, r.stderr[-3000:]


def test_ring_k3_k4_guard_bites_at_max_q_256():
    """Why test_ring_cges_trajectory_k3_k4 runs at max_q=512: at 256 the
    compiled-table guard bites one of its fused inits.  The host ring on the
    same seeds hands GES a fused init holding a q=324 family; the compiled
    scorer scores that graph -inf at max_q=256 while the float64 scorer and
    the delete-reduced family stay finite — the base-guarded, reduced-
    unguarded case in which host and compiled BES diverge
    (bdeu.graph_score_jax).  At max_q=512 the compiled total is finite and
    agrees with the float64 scorer."""
    from repro.core import GESConfig, fusion, ges_host, partition

    rng = np.random.default_rng(5)
    bn = random_bn(rng, n=9, n_edges=11, max_parents=2)
    data = forward_sample(bn, 400, rng)
    n, ar = bn.n, bn.arities

    def family_q(adj):
        return np.array([int(np.prod(ar[adj[:, y].astype(bool)]))
                         for y in range(n)])

    q_top, worst = 0, None
    cfg = GESConfig(max_q=512, counts_impl="segment")
    for k in (3, 4):
        masks = partition.partition_edges(data, ar, k)
        graphs = [np.zeros((n, n), np.int8)] * k
        best = -np.inf
        for _ in range(3):
            inits = [fusion.fusion_edge_union(graphs[i], graphs[(i - 1) % k])
                     .astype(np.int8) for i in range(k)]
            for init in inits:
                if family_q(init).max() > q_top:
                    q_top, worst = int(family_q(init).max()), init
            res = [ges_host(data, ar, init_adj=init, allowed=masks[i],
                            config=cfg) for i, init in enumerate(inits)]
            graphs = [r.adj for r in res]
            round_best = max(r.score for r in res)
            if round_best <= best + cfg.tol:
                break
            best = round_best
    assert q_top == 324

    dj, aj = _jnp(data, ar)
    exact = bdeu.graph_score_np(data, ar, worst)

    def compiled_total(max_q):
        return float(bdeu.graph_score_jax(dj, aj, jnp.asarray(worst), 10.0,
                                          max_q, int(ar.max()), "segment"))

    assert np.isfinite(exact)
    assert compiled_total(256) == -np.inf
    assert np.isclose(compiled_total(512), exact, rtol=1e-5)
    y = int(np.argmax(family_q(worst)))
    reduced = worst[:, y].astype(bool)
    reduced[np.flatnonzero(reduced)[0]] = False
    assert np.isfinite(float(bdeu.local_score_masked(
        dj, aj, jnp.int32(y), jnp.asarray(reduced), 10.0, 256,
        int(ar.max()), "segment")))
