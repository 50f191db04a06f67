"""The plain reference against first principles and against the program's
own host scorer at small sizes (the reference imports nothing of the
program; only these tests do)."""
import numpy as np
import pytest

from perfbench import data, reference as ref

CFG = {"n": 24, "n_edges": 32, "max_parents_true": 3,
       "arity_choices": [2, 3, 4], "arity_probs": [0.5, 0.3, 0.2],
       "concentration": 0.4, "network_seed": 3, "data_seed": 4, "m": 500}


@pytest.fixture(scope="module")
def problem():
    return data.problem(CFG, 5)


def test_family_score_matches_the_program_host_scorer(problem):
    from repro.core import bdeu

    for y, parents in ((0, []), (5, [1]), (7, [2, 9, 11])):
        want = bdeu.local_score_np(problem.data, problem.arities, y, parents)
        got = ref.family_score(problem.data, problem.arities, y, parents, 10.)
        assert got == pytest.approx(want, rel=1e-12)


def test_insert_column_equals_one_family_at_a_time(problem):
    d, ar = problem.data, problem.arities
    adj = np.zeros((24, 24), bool)
    adj[[1, 4], 6] = True
    col = ref.insert_column(d, ar, adj, 6, 10.0)
    base = ref.family_score(d, ar, 6, [1, 4], 10.0)
    for x in (0, 2, 23):
        want = ref.family_score(d, ar, 6, [1, 4, x], 10.0) - base
        assert col[x] == pytest.approx(want, rel=1e-12, abs=1e-9)


def test_similarity_matches_the_program(problem):
    from repro.core import bdeu

    want = bdeu.pairwise_similarity_fast(problem.data, problem.arities, 10.)
    got = ref.similarity(problem.data, problem.arities, 10.0)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-9)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_partition_equals_the_program_stage1(seed):
    from repro.core import partition

    p = data.problem(dict(CFG, data_seed=seed), seed)
    want = partition.partition_edges(p.data, p.arities, 4)
    got = ref.partition(p.data, p.arities, 4, 10.0)
    assert np.array_equal(got, want)


def test_reference_ges_is_a_local_optimum(problem):
    d, ar = problem.data, problem.arities
    adj, score = ref.ges(d, ar, 10.0, 6, 1024)
    assert ref.structure_faults(adj, ar, 6, 1024) == 0
    assert score == pytest.approx(ref.graph_score(d, ar, adj, 10.0))
    assert ref.delete_matrix(d, ar, adj, 10.0).max() <= 0


def test_bfloat16_is_the_lower_precision(problem):
    d, ar = problem.data, problem.arities
    adj, _ = ref.ges(d, ar, 10.0, 6, 1024)
    exact = ref.graph_score(d, ar, adj, 10.0)
    low = ref.graph_score(d, ar, adj, 10.0, "bfloat16")
    assert abs(low - exact) / abs(exact) > 1e-3


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_fusion_equals_the_program_fusion(seed):
    from repro.core import fusion

    rng = np.random.default_rng(seed)
    for _ in range(40):
        n = int(rng.integers(3, 40))
        own, pred = (data.random_dag(rng, n, int(rng.integers(0, 3 * n)),
                                     int(rng.integers(1, 5)))
                     for _ in range(2))
        want = fusion.fusion_edge_union(own.astype(np.int8),
                                        pred.astype(np.int8), engine="host")
        got = ref.fuse(own, pred)
        assert np.array_equal(got, np.asarray(want, dtype=bool))
        assert not np.diag(ref.reach(got)).any()
        # every adjacency of either input survives, in one direction
        skel = got | got.T
        assert not ((own | pred) & ~skel).any()


def test_fusion_of_an_empty_graph_is_the_other():
    g = np.zeros((5, 5), bool)
    g[0, 3] = g[2, 4] = True
    assert np.array_equal(ref.fuse(np.zeros_like(g), g), g)
    assert np.array_equal(ref.fuse(g, np.zeros_like(g)), g)


@pytest.mark.parametrize("limit", [None, 3])
def test_restricted_ges_equals_the_program_host_ges(problem, limit):
    """A member's step: GES inside a symmetric subset from a start, with
    FES capped, as the program's host GES makes it."""
    from repro.core import GESConfig, ges_host

    d, ar = problem.data, problem.arities
    n = d.shape[1]
    rng = np.random.default_rng(7)
    allowed = rng.random((n, n)) < 0.4
    allowed = allowed | allowed.T
    np.fill_diagonal(allowed, False)
    start = data.random_dag(rng, n, 10, 2)
    want = ges_host(d, ar, init_adj=start.astype(np.int8),
                    allowed=allowed, add_limit=limit,
                    config=GESConfig(ess=10.0))
    adj, score = ref.ges(d, ar, 10.0, 6, 1024, start=start, allowed=allowed,
                         limit=limit)
    assert np.array_equal(adj.astype(bool), np.asarray(want.adj, bool))
    assert score == pytest.approx(want.score, rel=1e-6)
    outside = ~allowed & ~np.eye(n, dtype=bool)
    assert np.array_equal(adj.astype(bool) & outside, start & outside)
    if limit is not None:
        assert int((adj.astype(bool) & ~start).sum()) <= limit


def test_add_limit_is_the_cges_l_limit():
    from repro.core.cges import edge_add_limit

    for n, k in ((441, 4), (724, 4), (16, 4), (100, 8)):
        assert ref.add_limit(n, k) == edge_add_limit(n, k)
