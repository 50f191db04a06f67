"""Fused all-candidate delta-sweep engine: equality vs the host oracle and the
per-candidate loop engine, overflow guard, restricted-subset columns, and
end-to-end trajectory identity of ges_jit across counts_impls."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import GESConfig, bdeu, ges_host, ges_jit
from repro.core.sweeps import sweep
from repro.data.bn import forward_sample, random_bn

FUSED_IMPLS = ["fused", "fused_pallas"]


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(11)
    bn = random_bn(rng, n=12, n_edges=14, max_parents=3)
    data = forward_sample(bn, 1500, rng)
    return bn, data


def _jnp_inputs(bn, data):
    return (jnp.asarray(data.astype(np.int32)),
            jnp.asarray(bn.arities.astype(np.int32)))


def test_bdeu_sweep_engines_share_one_counts_contract():
    """bdeu's in-module jnp fused path and the kernel package's oracle are
    separate implementations of the same counts contract — pin them to each
    other so neither can drift (the Pallas kernel is validated against the
    latter, production "fused" scoring uses the former)."""
    import jax

    from repro.kernels.bdeu_sweep import sweep_counts_ref

    key = jax.random.PRNGKey(3)
    m, n, q, r = 513, 9, 33, 4
    k1, k2, k3 = jax.random.split(key, 3)
    cfg = jax.random.randint(k1, (m,), 0, q, dtype=jnp.int32)
    child = jax.random.randint(k2, (m,), 0, r, dtype=jnp.int32)
    data = jax.random.randint(k3, (m, n), 0, r, dtype=jnp.int32)
    got = bdeu._sweep_counts_segment(cfg, child, bdeu._onehot_all(data, r),
                                     max_q=q, r_max=r)
    want = sweep_counts_ref(cfg, child, data, max_q=q, r_max=r)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_fused_column_matches_host_oracle(case, impl):
    """Fused sweep scores == per-family host oracle at every valid candidate."""
    bn, data = case
    dj, aj = _jnp_inputs(bn, data)
    n = bn.n
    y, pa = 2, [0, 5]
    pm = np.zeros(n, dtype=bool)
    pm[pa] = True
    scores = np.asarray(bdeu.fused_insert_scores(
        dj, aj, jnp.int32(y), jnp.asarray(pm), 10.0,
        max_q=256, r_max=int(bn.arities.max()), counts_impl=impl))
    for x in range(n):
        if x == y or pm[x]:
            continue  # garbage-by-convention entries (callers mask)
        want = bdeu.local_score_np(data, bn.arities, y, pa + [x])
        assert np.isclose(scores[x], want, rtol=2e-5, atol=1e-3), (x, impl)


@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_fused_deltas_match_segment(case, impl):
    """Full (n, n) insert-delta matrices agree with the loop engine
    everywhere (both engines share the duplicated-slot convention)."""
    bn, data = case
    dj, aj = _jnp_inputs(bn, data)
    n = bn.n
    adj = np.zeros((n, n), dtype=np.int8)
    adj[0, 2] = adj[5, 2] = adj[1, 4] = 1
    kw = dict(ess=10.0, max_q=256, r_max=int(bn.arities.max()))
    D_seg = np.asarray(bdeu.insert_deltas(
        dj, aj, jnp.asarray(adj), counts_impl="segment", **kw))
    D_fus = np.asarray(bdeu.insert_deltas(
        dj, aj, jnp.asarray(adj), counts_impl=impl, **kw))
    assert np.array_equal(np.isneginf(D_seg), np.isneginf(D_fus))
    finite = np.isfinite(D_seg)
    assert np.allclose(D_seg[finite], D_fus[finite], rtol=1e-4, atol=2e-3)


def test_fused_overflow_guard_matches_segment(case):
    """Candidates whose q0 * r_x exceeds max_q must be -inf, with the same
    guard mask as the loop engine (log-domain check)."""
    bn, data = case
    dj, aj = _jnp_inputs(bn, data)
    n = bn.n
    # 3 parents of arity >= 2 -> q0 >= 8; max_q=16 overflows most candidates
    adj = np.zeros((n, n), dtype=np.int8)
    adj[[0, 5, 7], 2] = 1
    kw = dict(ess=10.0, max_q=16, r_max=int(bn.arities.max()))
    D_seg = np.asarray(bdeu.insert_deltas(
        dj, aj, jnp.asarray(adj), counts_impl="segment", **kw))
    D_fus = np.asarray(bdeu.insert_deltas(
        dj, aj, jnp.asarray(adj), counts_impl="fused", **kw))
    assert np.isneginf(D_fus[:, 2]).any()       # the guard actually fires
    assert np.array_equal(np.isneginf(D_seg), np.isneginf(D_fus))


@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_fused_subset_column_matches_segment(case, impl):
    """Restricted-subset (pid_table) columns agree with the loop engine at
    EVERY entry: the sweep engine masks illegal toggles (self-pads, pids
    already in Pa_y) to -inf under both backends, so no caller-side masking
    is needed (regression for the old fused-path convention mismatch)."""
    bn, data = case
    dj, aj = _jnp_inputs(bn, data)
    n = bn.n
    adj = np.zeros((n, n), dtype=np.int8)
    adj[0, 3] = 1
    y = 3
    # pids include an existing parent (0) and self-padded tail entries
    pids = np.array([1, 0, 2, 5, 7, 9, y, y], dtype=np.int32)
    kw = dict(kind="insert", y=y, pids=jnp.asarray(pids), ess=10.0,
              max_q=256, r_max=int(bn.arities.max()))
    col_seg = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                               counts_impl="segment", **kw))
    col_fus = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                               counts_impl=impl, **kw))
    illegal = (pids == y) | (adj[pids, y] > 0)
    assert np.all(np.isneginf(col_seg[illegal]))
    assert np.all(np.isneginf(col_fus[illegal]))
    assert np.allclose(col_seg[~illegal], col_fus[~illegal],
                       rtol=1e-4, atol=2e-3)


def test_fused_incremental_column_matches_segment(case):
    """The incremental column-rescoring hot path (sweep with y, no pids)
    agrees across engines at every entry (illegal ones are -inf in both)."""
    bn, data = case
    dj, aj = _jnp_inputs(bn, data)
    n = bn.n
    adj = np.zeros((n, n), dtype=np.int8)
    adj[4, 1] = 1
    y = 1
    kw = dict(kind="insert", y=y, ess=10.0, max_q=256,
              r_max=int(bn.arities.max()))
    col_seg = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                               counts_impl="segment", **kw))
    col_fus = np.asarray(sweep(dj, aj, jnp.asarray(adj),
                               counts_impl="fused", **kw))
    illegal = np.zeros(n, dtype=bool)
    illegal[y] = True
    illegal[adj[:, y] > 0] = True
    assert np.all(np.isneginf(col_seg[illegal]))
    assert np.all(np.isneginf(col_fus[illegal]))
    assert np.allclose(col_seg[~illegal], col_fus[~illegal],
                       rtol=1e-4, atol=2e-3)


def test_ges_jit_trajectory_identity_across_impls(case):
    """The whole compiled FES+BES search must take the SAME greedy trajectory
    (same graph, same score) under the fused and loop engines."""
    bn, data = case
    dj, aj = _jnp_inputs(bn, data)
    n = bn.n
    z = jnp.zeros((n, n), jnp.int8)
    o = jnp.ones((n, n), jnp.int8)
    ref_adj = ref_score = None
    for impl in ["segment", "fused"]:
        cfg = GESConfig(max_q=256, counts_impl=impl)
        adj, score, _, _ = ges_jit(dj, aj, z, o, config=cfg)
        if ref_adj is None:
            ref_adj, ref_score = np.asarray(adj), float(score)
        else:
            assert np.array_equal(np.asarray(adj), ref_adj)
            assert abs(float(score) - ref_score) <= 1e-6 * abs(ref_score)


def test_ges_host_trajectory_identity_across_impls(case):
    """ges_host (the cGES host engine path) with fused columns reproduces the
    segment-engine trajectory and the host-oracle final score."""
    bn, data = case
    res_s = ges_host(data, bn.arities,
                     config=GESConfig(max_q=256, counts_impl="segment"))
    res_f = ges_host(data, bn.arities,
                     config=GESConfig(max_q=256, counts_impl="fused"))
    assert np.array_equal(res_s.adj, res_f.adj)
    assert np.isclose(res_s.score, res_f.score, rtol=1e-9)


# ---------------------------------------------------------------------------
# Row buckets of the fused reduction (bdeu._reduce_rows)
# ---------------------------------------------------------------------------

# Per family: the arities of a 12-column dataset, the child, and the parent
# sets giving each q0 on a side of an edge of q_ladder(1024) = (16, 64, 256,
# 1024).  17, 65 and 257 are no product of these arities, so each edge's
# upper side is the least q0 above it that is.
Q_CASES = {
    "pigs": ((3,) * 12, 11,
             {1: [], 9: [0, 1], 27: [0, 1, 2], 81: [0, 1, 2, 3],
              243: [0, 1, 2, 3, 4], 729: [0, 1, 2, 3, 4, 5],
              2187: [0, 1, 2, 3, 4, 5, 6]}),
    "link": ((4, 4, 4, 4, 4, 2, 3, 3, 2, 3, 4, 2), 10,
             {1: [], 16: [0, 1], 18: [5, 6, 7], 64: [0, 1, 2],
              72: [0, 5, 6, 7], 256: [0, 1, 2, 3], 288: [0, 1, 5, 6, 7],
              1024: [0, 1, 2, 3, 4], 1152: [0, 1, 2, 5, 6, 7]}),
}
Q_MAX = 1024


@pytest.fixture(scope="module")
def q_data():
    rng = np.random.default_rng(17)
    out = {}
    for fam, (ar, _, _) in Q_CASES.items():
        data = np.stack([rng.integers(0, a, size=2000) for a in ar], 1)
        out[fam] = (jnp.asarray(data.astype(np.int32)),
                    jnp.asarray(np.asarray(ar, dtype=np.int32)))
    return out


def _fused_column(dj, aj, child, pm, impl, kind, bucket_rows):
    fn = (bdeu.fused_insert_scores if kind == "insert"
          else bdeu.fused_delete_scores)
    return fn(dj, aj, child, pm, 10.0, Q_MAX, 4, impl,
              bucket_rows=bucket_rows)


_fused_column_jit = jax.jit(_fused_column,
                            static_argnames=("impl", "kind", "bucket_rows"))


@pytest.mark.parametrize("max_q, ladder", [
    (8, (8,)), (63, (63,)), (64, (16, 64)), (1000, (62, 250, 1000)),
    (1024, (16, 64, 256, 1024)), (4096, (16, 64, 256, 1024, 4096)),
    (1 << 20, (4096, 16384, 65536, 262144, 1 << 20))])
def test_q_ladder(max_q, ladder):
    """Buckets of max_q / 4^k rows, at least 16, at most five; q_bucket
    picks the least that holds q0, the last past max_q."""
    assert bdeu.q_ladder(max_q) == ladder
    q0 = np.array([1, *ladder, ladder[-1] + 1])
    got = np.asarray(bdeu.q_bucket(jnp.asarray(q0, jnp.float32), max_q))
    assert list(got) == [0, *range(len(ladder)), len(ladder) - 1]


@pytest.mark.parametrize("kind", ["insert", "delete"])
@pytest.mark.parametrize("impl", FUSED_IMPLS)
@pytest.mark.parametrize("fam, q0", [(f, q) for f, c in Q_CASES.items()
                                     for q in c[2]],
                         ids=lambda v: str(v))
def test_bucketed_reduction_matches_dense(q_data, fam, q0, impl, kind):
    """A column reduced over its q0 bucket equals the dense max_q-row
    reduction within float32 reassociation, with the same argmax over the
    legal candidates and the same -inf pattern (q0 * r_x > max_q inserts,
    overflowing families, q0 past max_q)."""
    ar, child, parents = Q_CASES[fam]
    assert int(np.prod([ar[p] for p in parents[q0]])) == q0
    dj, aj = q_data[fam]
    pm = np.zeros(len(ar), dtype=bool)
    pm[parents[q0]] = True
    got, dense = (np.asarray(_fused_column_jit(
        dj, aj, jnp.int32(child), jnp.asarray(pm), impl=impl, kind=kind,
        bucket_rows=b)) for b in (True, False))
    assert np.array_equal(np.isneginf(got), np.isneginf(dense))
    fin = np.isfinite(dense)
    assert np.allclose(got[fin], dense[fin], rtol=1e-6, atol=0)
    legal = ~pm if kind == "insert" else pm.copy()
    legal[child] = False
    if (legal & fin).any():
        masked = lambda s: np.where(legal, s, -np.inf)
        assert np.argmax(masked(got)) == np.argmax(masked(dense))
    if kind == "insert" and q0 >= Q_MAX:
        assert np.isneginf(got).all()           # past the table: stays -inf


def _bucket_conds(jaxpr, max_q):
    """Index avals of the ``cond`` equations with one branch per bucket."""
    from repro.analysis.contracts import iter_eqns

    n_buckets = len(bdeu.q_ladder(max_q))
    return [e.invars[0].aval for e in iter_eqns(jaxpr)
            if e.primitive.name == "cond"
            and len(e.params["branches"]) == n_buckets]


@pytest.mark.parametrize("restricted", [False, True],
                         ids=["full", "restricted"])
@pytest.mark.parametrize("kind", ["insert", "delete"])
@pytest.mark.parametrize("impl", FUSED_IMPLS)
def test_child_sweeps_switch_on_an_unbatched_bucket(case, impl, kind,
                                                    restricted):
    """The fused matrix sweeps map children one at a time without vmap, so
    the row-bucket switch stays a ``cond`` on a scalar index: under vmap a
    batched index becomes a select that runs every branch.  Children
    batched wider than one (child_chunk >= n) reduce densely instead: no
    bucket cond at all."""
    from repro.core.partition import pid_table_from_allowed
    from repro.core.sweeps import (sweep_matrix_body,
                                   sweep_matrix_restricted_body)

    bn, data = case
    dj, aj = _jnp_inputs(bn, data)
    n = bn.n
    adj = jnp.zeros((n, n), jnp.int8).at[0, 2].set(1)
    kw = dict(ess=10.0, max_q=Q_MAX, r_max=int(bn.arities.max()),
              counts_impl=impl, kind=kind)
    if restricted:
        allowed = ~np.eye(n, dtype=bool)
        tbl = jnp.asarray(pid_table_from_allowed(allowed))
        body = lambda chunk: (lambda a: sweep_matrix_restricted_body(
            dj, aj, a, tbl, child_chunk=chunk, **kw))
    else:
        body = lambda chunk: (lambda a: sweep_matrix_body(
            dj, aj, a, child_chunk=chunk, **kw))
    conds = _bucket_conds(jax.make_jaxpr(body(None))(adj), Q_MAX)
    assert conds and all(aval.shape == () for aval in conds)
    assert not _bucket_conds(jax.make_jaxpr(body(n))(adj), Q_MAX)
