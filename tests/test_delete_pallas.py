"""VMEM-resident Pallas delete sweep (kernels/bdeu_sweep.delete_marginals):
kernel == jnp oracle == loop/segment engines over random arities, padded
r_max, empty parent sets, the max_q +/-inf guard and restricted-W pids —
through both the column and full-matrix sweep entry points — plus a seeded
ring_cges trajectory pin under counts_impl="fused_pallas"."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import bdeu
from repro.core.sweeps import sweep


def _jnp(data, arities):
    return (jnp.asarray(data.astype(np.int32)),
            jnp.asarray(arities.astype(np.int32)))


def _random_case(seed, n_lo=4, n_hi=10):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(n_lo, n_hi))
    m = int(rng.integers(80, 300))
    arities = rng.integers(2, 5, size=n)
    data = np.stack([rng.integers(0, a, size=m) for a in arities], 1)
    order = rng.permutation(n)
    adj = np.zeros((n, n), dtype=np.int8)
    for j in range(1, n):
        y = order[j]
        k = int(rng.integers(0, min(3, j) + 1))
        for x in rng.choice(order[:j], size=k, replace=False):
            adj[x, y] = 1
    return rng, n, arities, data, adj


# ---------------------------------------------------------------------------
# Kernel-level: Pallas (interpret) vs the jnp oracle, exact contract
# ---------------------------------------------------------------------------

@given(st.integers(min_value=0, max_value=10**6))
@settings(max_examples=6, deadline=None)
def test_delete_kernel_matches_ref_oracle(seed):
    """delete_marginals (Pallas interpret) == its segment-sum oracle
    exactly on random families, including identity-padded slots and m
    padding, and the tables score to the host oracle's deletions."""
    from repro.kernels.bdeu_sweep import delete_marginals

    rng, n, arities, data, _ = _random_case(seed)
    max_q = 64
    pa = rng.choice(n, size=min(3, n), replace=False)
    pm = np.zeros(n, dtype=bool)
    pm[pa] = True
    dj, aj = _jnp(data, arities)
    cfg, q0 = bdeu._slot_encode(dj, aj, jnp.asarray(pm))
    cfgc = jnp.clip(cfg, 0, max_q - 1)
    child = int(rng.integers(0, n))
    child_col = dj[:, child]

    slot_ar = np.where(pm, arities, 1).astype(np.int32)
    low = np.concatenate(
        [np.cumprod(slot_ar[::-1])[::-1][1:], np.ones(1, np.int32)]
    ).astype(np.int32)
    n_slots = 4
    ids = np.sort(pa)[:n_slots]
    ar_s = np.ones(n_slots, np.int32)
    low_s = np.ones(n_slots, np.int32)
    ar_s[:ids.size] = slot_ar[ids]
    low_s[:ids.size] = low[ids]
    q_slots = np.concatenate([[int(q0)], int(q0) // ar_s])
    cand_slot = np.zeros(n, np.int32)
    cand_slot[ids] = 1 + np.arange(ids.size)

    kw = dict(max_q=max_q, r_max=int(arities.max()))
    tables = delete_marginals(cfgc, child_col, jnp.asarray(ar_s),
                              jnp.asarray(low_s), **kw)
    want = delete_marginals(cfgc, child_col, jnp.asarray(ar_s),
                            jnp.asarray(low_s), use_ref=True, **kw)
    assert tables.shape == want.shape == (n_slots + 1, max_q, kw["r_max"])
    assert np.array_equal(np.asarray(tables), np.asarray(want)), seed
    slot_scores = bdeu._bdeu_from_counts(tables, jnp.asarray(q_slots),
                                         int(arities[child]), 10.0)
    got = np.asarray(slot_scores)[cand_slot]
    # per-family host oracle at each real deletion
    base = bdeu.local_score_np(data, arities, child, list(np.sort(pa)))
    assert np.allclose(got[cand_slot == 0], base, rtol=1e-4, atol=2e-3)
    for x in ids:
        ref = bdeu.local_score_np(
            data, arities, child, [p for p in np.sort(pa) if p != x])
        assert np.isclose(got[x], ref, rtol=1e-4, atol=2e-3), (seed, x)


# ---------------------------------------------------------------------------
# Engine-level: fused_pallas delete columns/matrices vs the loop engine
# ---------------------------------------------------------------------------

@given(st.integers(min_value=0, max_value=10**6))
@settings(max_examples=6, deadline=None)
def test_delete_pallas_columns_match_loop(seed):
    """Random arities/graphs: the VMEM-resident fused_pallas delete column
    agrees with the loop engine entry-for-entry (masking included), and with
    the host oracle at every legal entry."""
    rng, n, arities, data, adj = _random_case(seed)
    dj, aj = _jnp(data, arities)
    y = int(rng.integers(0, n))
    kw = dict(kind="delete", y=y, ess=10.0, max_q=256,
              r_max=int(arities.max()))
    col_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                                counts_impl="segment", **kw))
    col_pal = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                               counts_impl="fused_pallas", **kw))
    assert np.array_equal(np.isneginf(col_loop), np.isneginf(col_pal)), seed
    f = np.isfinite(col_loop)
    assert np.allclose(col_loop[f], col_pal[f], rtol=1e-4, atol=2e-3), seed
    pm = adj[:, y].astype(bool)
    base = bdeu.local_score_np(data, arities, y, list(np.flatnonzero(pm)))
    for x in np.flatnonzero(pm):
        want = bdeu.local_score_np(
            data, arities, y,
            [p for p in np.flatnonzero(pm) if p != x]) - base
        assert np.isclose(col_pal[x], want, rtol=1e-4, atol=2e-3), (seed, x)


@pytest.mark.parametrize("max_q", [300, 384, 512])
def test_delete_pallas_nonmultiple_max_q_chunking(max_q):
    """max_q above the 256-row chunk — including values 256 does NOT divide
    (300, 384) — must marginalize correctly: the final chunk is shifted back
    in bounds and its overlap rows masked, so every row scatters exactly
    once (vs the loop engine, which never chunks)."""
    rng, n, arities, data, adj = _random_case(23)
    dj, aj = _jnp(data, arities)
    y = int(np.flatnonzero(adj.sum(axis=0))[0])        # a child with parents
    kw = dict(kind="delete", y=y, ess=10.0, max_q=max_q,
              r_max=int(arities.max()))
    col_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                                counts_impl="segment", **kw))
    col_pal = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                               counts_impl="fused_pallas", **kw))
    assert np.array_equal(np.isneginf(col_loop), np.isneginf(col_pal))
    f = np.isfinite(col_loop)
    assert f.any()
    assert np.allclose(col_loop[f], col_pal[f], rtol=1e-4, atol=2e-3)


def test_delete_pallas_empty_parent_set():
    """Empty Pa: the whole fused_pallas column is -inf (no legal deletes),
    no NaNs — the all-identity-slot path through the kernel."""
    rng, n, arities, data, _ = _random_case(3)
    adj = np.zeros((n, n), dtype=np.int8)
    dj, aj = _jnp(data, arities)
    col = np.asarray(sweep(dj, aj, jnp.asarray(adj), kind="delete", y=1,
                           ess=10.0, max_q=64, r_max=int(arities.max()),
                           counts_impl="fused_pallas"))
    assert np.all(np.isneginf(col))
    assert not np.isnan(col).any()


def test_delete_pallas_max_q_guard():
    """The +/-inf guard conventions of the kernel path equal the loop
    engine's exactly, including families whose own q0 overflows max_q
    (finite entries become +inf deltas, doubly-overflowing ones NaN)."""
    data = np.stack([np.random.default_rng(0).integers(0, a, size=400)
                     for a in (3, 4, 4, 2, 2)], 1)
    arities = np.array([3, 4, 4, 2, 2])
    n = arities.size
    adj = np.zeros((n, n), dtype=np.int8)
    adj[[0, 1, 2], 4] = 1                        # q0 = 48
    dj, aj = _jnp(data, arities)
    for max_q in (24, 12):                       # both overflow q0 = 48
        kw = dict(kind="delete", y=4, ess=10.0, max_q=max_q,
                  r_max=int(arities.max()))
        col_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                                    counts_impl="segment", **kw))
        col_pal = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                                   counts_impl="fused_pallas", **kw))
        assert np.array_equal(np.isposinf(col_loop), np.isposinf(col_pal))
        assert np.array_equal(np.isneginf(col_loop), np.isneginf(col_pal))
        assert np.array_equal(np.isnan(col_loop), np.isnan(col_pal))


@given(st.integers(min_value=0, max_value=10**6))
@settings(max_examples=4, deadline=None)
def test_delete_pallas_restricted_pids(seed):
    """Restricted (W,) columns (ring E_i subsets incl. self-pads) under
    fused_pallas == loop engine, and the (W, n) pid_table matrix entry
    point routes through the same kernel."""
    from repro.core.partition import pid_table_from_allowed

    rng, n, arities, data, adj = _random_case(seed)
    dj, aj = _jnp(data, arities)
    y = int(rng.integers(0, n))
    W = int(rng.integers(1, n + 1))
    pids = np.full(W, y, dtype=np.int32)
    real = rng.choice(n, size=int(rng.integers(0, W)), replace=False)
    pids[:real.size] = real
    kw = dict(kind="delete", ess=10.0, max_q=256, r_max=int(arities.max()))
    col_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj), y=y,
                                pids=jnp.asarray(pids),
                                counts_impl="segment", **kw))
    col_pal = np.asarray(sweep(dj, aj, jnp.asarray(adj), y=y,
                               pids=jnp.asarray(pids),
                               counts_impl="fused_pallas", **kw))
    assert col_pal.shape == (W,)
    assert np.array_equal(np.isneginf(col_loop), np.isneginf(col_pal)), seed
    f = np.isfinite(col_loop)
    assert np.allclose(col_loop[f], col_pal[f], rtol=1e-4, atol=2e-3), seed

    allowed = rng.random((n, n)) < 0.5
    np.fill_diagonal(allowed, False)
    tbl = pid_table_from_allowed(allowed)
    D_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                              pid_table=jnp.asarray(tbl),
                              counts_impl="segment", **kw))
    D_pal = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                             pid_table=jnp.asarray(tbl),
                             counts_impl="fused_pallas", **kw))
    assert np.array_equal(np.isneginf(D_loop), np.isneginf(D_pal)), seed
    f = np.isfinite(D_loop)
    assert np.allclose(D_loop[f], D_pal[f], rtol=1e-4, atol=2e-3), seed


def test_delete_pallas_full_matrix_entry_point():
    """The full (n, n) BES initialization matrix under fused_pallas (the
    vmapped kernel path of bdeu._deltas_impl) == loop engine everywhere."""
    rng, n, arities, data, adj = _random_case(17)
    dj, aj = _jnp(data, arities)
    kw = dict(kind="delete", ess=10.0, max_q=256, r_max=int(arities.max()))
    D_loop = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                              counts_impl="segment", **kw))
    D_pal = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                             counts_impl="fused_pallas", **kw))
    assert np.array_equal(np.isneginf(D_loop), np.isneginf(D_pal))
    f = np.isfinite(D_loop)
    assert np.allclose(D_loop[f], D_pal[f], rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# End-to-end: seeded ring trajectory pin under fused_pallas
# ---------------------------------------------------------------------------

def test_ring_cges_fused_pallas_trajectory_pin():
    """Seeded ring_cges on k in {1, 2} meshes: the compiled restricted ring
    under counts_impl="fused_pallas" (every BES delete column through the
    VMEM-resident kernel) is trajectory-identical to the segment engine —
    same best graphs, same scores, same round count (subprocess: needs a
    multi-device host platform)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import sys
        sys.path.insert(0, "src")
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import GESConfig, partition
        from repro.core.ring import RingSpec, ring_cges
        from repro.data.bn import forward_sample, random_bn

        rng = np.random.default_rng(7)
        bn = random_bn(rng, n=8, n_edges=9, max_parents=2)
        data = forward_sample(bn, 400, rng)
        for k in (1, 2):
            masks = partition.partition_edges(data, bn.arities, k)
            mesh = Mesh(np.array(jax.devices()[:k]), ("ring",))
            spec = RingSpec(k=k, max_rounds=3)
            out = {}
            for impl in ("segment", "fused_pallas"):
                cfg = GESConfig(max_q=64, counts_impl=impl)
                out[impl] = ring_cges(data, bn.arities, masks, mesh, spec,
                                      cfg, restricted=True)
            gS, sS, rS = out["segment"]
            gP, sP, rP = out["fused_pallas"]
            assert np.array_equal(gS, gP), (k, "adjacency drift")
            assert np.allclose(sS, sP, rtol=1e-5), (k, "score drift")
            assert rS == rP, (k, "round-count drift")
            assert gP.any()          # the ring actually learned something
        print("PALLAS_RING_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "PALLAS_RING_OK" in r.stdout, r.stderr[-3000:]
