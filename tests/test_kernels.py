"""Per-kernel allclose vs pure-jnp oracle, swept over shapes/dtypes
(hypothesis + parametrized grids).  Pallas kernels run in interpret mode."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.bdeu_count import contingency_counts, contingency_counts_ref
from repro.kernels.bdeu_sweep import sweep_counts
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.ssd_scan import ssd_scan, ssd_scan_ref


# ---------------------------------------------------------------------------
# bdeu_count
# ---------------------------------------------------------------------------

@given(st.integers(0, 10**6), st.integers(1, 700), st.integers(2, 7),
       st.integers(4, 90))
@settings(max_examples=20, deadline=None)
def test_bdeu_count_matches_ref(seed, m, r, q):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    cfg = jax.random.randint(k1, (m,), 0, q, dtype=jnp.int32)
    child = jax.random.randint(k2, (m,), 0, r, dtype=jnp.int32)
    got = contingency_counts(cfg, child, max_q=q, r_max=r, tile_m=128)
    want = contingency_counts_ref(cfg, child, max_q=q, r_pad=r)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_bdeu_count_total_mass():
    cfg = jnp.zeros((1000,), jnp.int32)
    child = jnp.ones((1000,), jnp.int32)
    counts = contingency_counts(cfg, child, max_q=4, r_max=3)
    assert float(counts.sum()) == 1000.0
    assert float(counts[0, 1]) == 1000.0


# ---------------------------------------------------------------------------
# bdeu_sweep (fused all-candidate contraction)
# ---------------------------------------------------------------------------

@given(st.integers(0, 10**6), st.integers(1, 500), st.integers(2, 5),
       st.integers(4, 60), st.integers(1, 50))
@settings(max_examples=15, deadline=None)
def test_bdeu_sweep_matches_ref(seed, m, r, q, n):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    cfg = jax.random.randint(k1, (m,), 0, q, dtype=jnp.int32)
    child = jax.random.randint(k2, (m,), 0, r, dtype=jnp.int32)
    data = jax.random.randint(k3, (m, n), 0, r, dtype=jnp.int32)
    got = sweep_counts(cfg, child, data, max_q=q, r_max=r, tile_m=128)
    want = sweep_counts(cfg, child, data, max_q=q, r_max=r, use_ref=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_bdeu_sweep_total_mass_and_blocks():
    """Every (b, x) block sums to the number of instances with child=b; the
    whole tensor sums to m * n (each instance counted once per variable)."""
    m, n, q, r = 640, 5, 8, 3
    key = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(key, 3)
    cfg = jax.random.randint(k1, (m,), 0, q, dtype=jnp.int32)
    child = jax.random.randint(k2, (m,), 0, r, dtype=jnp.int32)
    data = jax.random.randint(k3, (m, n), 0, r, dtype=jnp.int32)
    counts = np.asarray(sweep_counts(cfg, child, data, max_q=q, r_max=r))
    assert counts.shape == (r, r, q, n)
    assert float(counts.sum()) == float(m * n)
    child_np = np.asarray(child)
    per_b = counts.sum(axis=(1, 2))                      # (b, x)
    for b in range(r):
        assert np.all(per_b[b] == np.sum(child_np == b))


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,t,d", [
    (1, 4, 4, 128, 64),     # MHA, exact blocks
    (2, 8, 2, 256, 64),     # GQA 4:1
    (1, 4, 1, 200, 32),     # MQA, ragged seq (padding path)
    (1, 16, 8, 384, 128),   # GQA 2:1, bigger head_dim
])
def test_flash_attention_matches_ref(b, hq, hkv, t, d, dtype):
    key = jax.random.PRNGKey(hq * t + d)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, t, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, t, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, t, d), dtype)
    got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    want = attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@given(st.integers(0, 10**6))
@settings(max_examples=8, deadline=None)
def test_flash_attention_random_shapes(seed):
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 3))
    hkv = int(rng.choice([1, 2, 4]))
    group = int(rng.choice([1, 2, 4]))
    t = int(rng.integers(16, 300))
    d = int(rng.choice([32, 64]))
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hkv * group, t, d))
    k = jax.random.normal(ks[1], (b, hkv, t, d))
    v = jax.random.normal(ks[2], (b, hkv, t, d))
    got = flash_attention(q, k, v, causal=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,t,p,n,chunk", [
    (1, 2, 128, 32, 16, 64),
    (2, 4, 256, 64, 32, 128),
    (1, 1, 100, 16, 8, 32),     # ragged (padding path)
])
def test_ssd_scan_matches_ref(b, h, t, p, n, chunk, dtype):
    key = jax.random.PRNGKey(t + p)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, h, t, p), dtype)
    a = -jax.nn.softplus(jax.random.normal(ks[1], (b, h, t)))
    bm = jax.random.normal(ks[2], (b, h, t, n), dtype) * 0.3
    cm = jax.random.normal(ks[3], (b, h, t, n), dtype) * 0.3
    got = ssd_scan(x, a, bm, cm, chunk=chunk)
    want = ssd_scan_ref(x, a, bm, cm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@given(st.integers(0, 10**6))
@settings(max_examples=6, deadline=None)
def test_ssd_scan_random_shapes(seed):
    rng = np.random.default_rng(seed)
    b, h = int(rng.integers(1, 3)), int(rng.integers(1, 4))
    t = int(rng.integers(10, 200))
    p = int(rng.choice([16, 32]))
    n = int(rng.choice([8, 16]))
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, h, t, p))
    a = -jax.nn.softplus(jax.random.normal(ks[1], (b, h, t)))
    bm = jax.random.normal(ks[2], (b, h, t, n)) * 0.3
    cm = jax.random.normal(ks[3], (b, h, t, n)) * 0.3
    got = ssd_scan(x, a, bm, cm, chunk=64)
    want = ssd_scan_ref(x, a, bm, cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def test_ssd_chunk_stitching_matches_single_chunk():
    """Cross-chunk state passing: chunk=T vs chunk=T/4 must agree exactly."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    b, h, t, p, n = 1, 2, 128, 16, 8
    x = jax.random.normal(ks[0], (b, h, t, p))
    a = -jax.nn.softplus(jax.random.normal(ks[1], (b, h, t)))
    bm = jax.random.normal(ks[2], (b, h, t, n)) * 0.3
    cm = jax.random.normal(ks[3], (b, h, t, n)) * 0.3
    big = ssd_scan(x, a, bm, cm, chunk=128)
    small = ssd_scan(x, a, bm, cm, chunk=32)
    np.testing.assert_allclose(np.asarray(big), np.asarray(small),
                               rtol=2e-4, atol=2e-4)
