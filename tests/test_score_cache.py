"""Persistent family-score cache (core/score_cache + driver wiring):
exact-key probe/insert round-trips, prioritized eviction, hit-path
semantics of ``lookup_or_compute``, the ``REPRO_FAMILY_CACHE`` call-time
env default, and cached-vs-uncached trajectory pins for ges_host,
ges_jit (full-n and pid_table-restricted), cges (both engines) and the
compiled ring (subprocess, multi-device).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import DeviceFamilyCache, GESConfig, cges, ges_host, ges_jit
from repro.core import score_cache as sc

from _hypothesis_compat import given, settings, st

N_VARS = 12


def _mask_from_int(bits: int) -> jnp.ndarray:
    return jnp.asarray([(bits >> i) & 1 for i in range(N_VARS)], jnp.int32)


def _key_tuple(seed: int):
    return (seed % 2,                       # kind
            (seed // 2) % N_VARS,           # child
            seed % (1 << N_VARS),           # parent mask bits
            (seed * 31) % 97)               # scope


def test_probe_insert_roundtrip():
    cache = sc.init(N_VARS, width=N_VARS, capacity=64)
    col = jnp.arange(N_VARS, dtype=jnp.float32) - 3.0
    mask = _mask_from_int(0b1010)
    hit, _, cache = sc.probe(cache, sc.KIND_INSERT, 2, mask, 0)
    assert not bool(hit)
    cache = sc.insert(cache, sc.KIND_INSERT, 2, mask, 0, col)
    hit, got, cache = sc.probe(cache, sc.KIND_INSERT, 2, mask, 0)
    assert bool(hit)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(col))
    # every key word must participate in matching: perturb each component
    for kind, child, scope in [(sc.KIND_DELETE, 2, 0), (sc.KIND_INSERT, 3, 0),
                               (sc.KIND_INSERT, 2, 1)]:
        h, _, cache = sc.probe(cache, kind, child, mask, scope)
        assert not bool(h), (kind, child, scope)
    h, _, cache = sc.probe(cache, sc.KIND_INSERT, 2, _mask_from_int(0b1011), 0)
    assert not bool(h)
    st_ = sc.stats(cache)
    assert st_["hits"] == 1 and st_["misses"] == 1 and st_["occupied"] == 1


@settings(max_examples=24, deadline=None)
@given(st.integers(min_value=0, max_value=10**6),
       st.integers(min_value=0, max_value=10**6))
def test_key_packing_exact(a, b):
    """Packed keys are equal word-for-word IFF the (kind, child, mask,
    scope) tuples are equal — the no-collision contract that makes cached
    trajectories bitwise-identical."""
    ta, tb = _key_tuple(a), _key_tuple(b)
    ka = sc._pack_key(ta[0], ta[1], _mask_from_int(ta[2]), ta[3])
    kb = sc._pack_key(tb[0], tb[1], _mask_from_int(tb[2]), tb[3])
    assert bool(jnp.all(ka == kb)) == (ta == tb)


def test_eviction_prefers_low_priority_and_probe_refreshes():
    """capacity == WAYS -> a single set: inserting WAYS+1 keys evicts the
    min-priority way, and a probe hit refreshes recency so the re-touched
    entry survives while the stalest one is evicted."""
    cache = sc.init(N_VARS, width=4, capacity=sc.WAYS)
    neg = jnp.full((4,), -jnp.inf, jnp.float32)   # sigmoid gain bonus = 0
    for i in range(sc.WAYS):
        cache = sc.insert(cache, 0, i, _mask_from_int(0), 0, neg)
    assert sc.stats(cache)["occupied"] == sc.WAYS
    # refresh key child=0 (inserted first, currently stalest)
    hit, _, cache = sc.probe(cache, 0, 0, _mask_from_int(0), 0)
    assert bool(hit)
    cache = sc.insert(cache, 0, sc.WAYS, _mask_from_int(0), 0, neg)
    assert sc.stats(cache)["occupied"] == sc.WAYS
    hit0, _, cache = sc.probe(cache, 0, 0, _mask_from_int(0), 0)
    assert bool(hit0)                   # refreshed -> survived
    hit1, _, cache = sc.probe(cache, 0, 1, _mask_from_int(0), 0)
    assert not bool(hit1)               # stalest un-refreshed way evicted


def test_positive_gain_column_outranks_exhausted_column():
    """The PER-flavoured bonus: at the same access step, a column that
    still contains a positive score delta gets strictly higher eviction
    priority than one whose every toggle is masked/non-improving."""
    step = jnp.int32(7)
    improving = sc._priority(step, jnp.asarray([-1.0, 0.5], jnp.float32))
    exhausted = sc._priority(step, jnp.asarray([-jnp.inf, -2.0], jnp.float32))
    assert float(improving) > float(exhausted)
    assert float(improving) - float(exhausted) <= sc.GAIN_WEIGHT + 1e-6


def test_lookup_or_compute_hit_returns_cached_column():
    cache = sc.init(N_VARS, width=3, capacity=32)
    mask = _mask_from_int(0b11)
    col0 = jnp.asarray([1.0, -2.0, 0.5], jnp.float32)
    got0, cache = sc.lookup_or_compute(cache, 0, 1, mask, 0, lambda: col0)
    np.testing.assert_array_equal(np.asarray(got0), np.asarray(col0))
    # same key, different compute closure: the CACHED column must win
    decoy = jnp.asarray([9.0, 9.0, 9.0], jnp.float32)
    got1, cache = sc.lookup_or_compute(cache, 0, 1, mask, 0, lambda: decoy)
    np.testing.assert_array_equal(np.asarray(got1), np.asarray(col0))
    st_ = sc.stats(cache)
    assert st_["hits"] == 1 and st_["misses"] == 1


def test_family_cache_env_default_read_at_call_time(monkeypatch):
    """GESConfig.family_cache defaults from REPRO_FAMILY_CACHE at
    INSTANTIATION time (default_factory), so the CI leg's env flip works
    even when the var is set after ``import repro``."""
    monkeypatch.delenv("REPRO_FAMILY_CACHE", raising=False)
    assert GESConfig().family_cache is False
    monkeypatch.setenv("REPRO_FAMILY_CACHE", "1")
    assert GESConfig().family_cache is True
    monkeypatch.setenv("REPRO_FAMILY_CACHE", "0")
    assert GESConfig().family_cache is False


def test_cache_capacity_validation():
    with pytest.raises(ValueError):
        GESConfig(cache_capacity=0)


def _dataset(seed=5, n=9, m=240):
    rng = np.random.default_rng(seed)
    arities = rng.integers(2, 4, size=n).astype(np.int64)
    data = np.stack([rng.integers(0, a, size=m) for a in arities], 1)
    return data.astype(np.int64), arities


def test_ges_host_cached_trajectory_identical():
    data, arities = _dataset()
    n = arities.size
    # family_cache pinned False: under the REPRO_FAMILY_CACHE=1 CI leg the
    # env default would otherwise silently cache the "uncached" baseline
    base = ges_host(data, arities,
                    config=GESConfig(max_q=64, counts_impl="fused",
                                     family_cache=False))
    fc = DeviceFamilyCache(n, capacity=512)
    r1 = ges_host(data, arities,
                  config=GESConfig(max_q=64, counts_impl="fused",
                                   family_cache=True, cache_capacity=512),
                  family_cache=fc)
    assert np.array_equal(base.adj, r1.adj)
    assert base.score == r1.score
    st1 = fc.stats()
    assert st1["misses"] > 0
    # second run through the SAME handle: warm, hit-dominated, identical
    r2 = ges_host(data, arities,
                  config=GESConfig(max_q=64, counts_impl="fused",
                                   family_cache=True, cache_capacity=512),
                  family_cache=fc)
    assert np.array_equal(base.adj, r2.adj) and base.score == r2.score
    st2 = fc.stats()
    assert st2["hits"] > st1["hits"]
    assert st2["misses"] == st1["misses"]    # nothing new to compute


def test_ges_host_rejects_mismatched_cache_width():
    data, arities = _dataset()
    with pytest.raises(ValueError, match="family_cache"):
        ges_host(data, arities,
                 config=GESConfig(max_q=64, family_cache=True),
                 family_cache=DeviceFamilyCache(arities.size + 1))


@pytest.mark.parametrize("incremental", [True, False])
def test_ges_jit_cached_trajectory_identical(incremental):
    """Compiled engine: cache on/off bitwise-identical (adjacency AND
    score), warm restart via the returned cache pytree is hit-dominated."""
    data, arities = _dataset(seed=7, n=8, m=160)
    n = arities.size
    allowed = ~np.eye(n, dtype=bool)
    init = np.zeros((n, n), np.int8)
    kw = dict(config=GESConfig(max_q=64, counts_impl="segment",
                               incremental=incremental, family_cache=False))
    a0, s0, _, _ = ges_jit(data, arities, init, allowed, **kw)
    cfg_c = GESConfig(max_q=64, counts_impl="segment",
                      incremental=incremental, family_cache=True,
                      cache_capacity=256)
    a1, s1, _, _, cache = ges_jit(data, arities, init, allowed,
                                  config=cfg_c, return_cache=True)
    assert np.array_equal(np.asarray(a0), np.asarray(a1))
    assert float(s0) == float(s1)
    st1 = sc.stats(cache)
    a2, s2, _, _, cache2 = ges_jit(data, arities, init, allowed,
                                   config=cfg_c, cache=cache,
                                   return_cache=True)
    assert np.array_equal(np.asarray(a0), np.asarray(a2))
    assert float(s0) == float(s2)
    st2 = sc.stats(cache2)
    assert st2["hits"] > st1["hits"]


def test_ges_jit_restricted_cached_trajectory_identical():
    from repro.core.partition import pid_table_from_allowed

    data, arities = _dataset(seed=9, n=8, m=160)
    n = arities.size
    rng = np.random.default_rng(0)
    allowed = np.zeros((n, n), bool)
    for y in range(n):
        cands = rng.choice([x for x in range(n) if x != y], 4, replace=False)
        allowed[cands, y] = True
    pt = jnp.asarray(np.asarray(pid_table_from_allowed(allowed)))
    init = np.zeros((n, n), np.int8)
    a0, s0, _, _ = ges_jit(data, arities, init, allowed,
                           config=GESConfig(max_q=64, counts_impl="fused",
                                            family_cache=False),
                           pid_table=pt)
    a1, s1, _, _, cache = ges_jit(
        data, arities, init, allowed,
        config=GESConfig(max_q=64, counts_impl="fused", family_cache=True,
                         cache_capacity=256),
        pid_table=pt, return_cache=True)
    assert np.array_equal(np.asarray(a0), np.asarray(a1))
    assert float(s0) == float(s1)
    assert sc.stats(cache)["misses"] > 0


@pytest.mark.parametrize("engine", ["host", "jax"])
def test_cges_cached_trajectory_identical(engine):
    data, arities = _dataset(seed=11, n=9, m=200)
    r0 = cges(data, arities, k=3, engine=engine,
              config=GESConfig(max_q=64, counts_impl="fused",
                               family_cache=False))
    r1 = cges(data, arities, k=3, engine=engine,
              config=GESConfig(max_q=64, counts_impl="fused",
                               family_cache=True, cache_capacity=2048))
    assert np.array_equal(r0.adj, r1.adj)
    assert r0.score == r1.score
    assert r0.rounds == r1.rounds
    assert r0.family_cache_stats is None
    st_ = r1.family_cache_stats
    assert st_ is not None and st_["hits"] > 0
    # ring members + rounds + fine-tune share families: real reuse
    assert st_["hit_rate"] > 0.2


def test_ring_cached_trajectory_subprocess():
    """Compiled shard_map ring, cache threaded through the round
    while_loop: trajectory identical to uncached, per-process hit stats
    returned, hit rate substantial (>= 0.3 at this tiny scale)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import sys
        sys.path.insert(0, "src")
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import partition
        from repro.core.ges import GESConfig
        from repro.core.ring import RingSpec, ring_cges

        rng = np.random.default_rng(3)
        n, m, k = 10, 240, 2
        arities = rng.integers(2, 4, size=n).astype(np.int64)
        data = np.stack([rng.integers(0, a, size=m) for a in arities], 1)
        masks = partition.partition_edges(data, arities, k)
        mesh = Mesh(np.array(jax.devices())[:k], ("ring",))
        spec = RingSpec(k=k, max_rounds=8)

        g0, s0, r0 = ring_cges(data, arities, masks, mesh, spec,
                               GESConfig(max_q=64, counts_impl="fused",
                                         family_cache=False))
        cfg = GESConfig(max_q=64, counts_impl="fused", family_cache=True,
                        cache_capacity=1024)
        g1, s1, r1, stats = ring_cges(data, arities, masks, mesh, spec, cfg,
                                      return_cache_stats=True)
        assert np.array_equal(g0, g1)
        assert np.array_equal(s0, s1)
        assert r0 == r1
        assert len(stats) == k
        rates = [st["hit_rate"] for st in stats]
        assert all(st["hits"] > 0 for st in stats), stats
        assert max(rates) >= 0.3, stats
        # stats without the cache flag must fail loudly
        try:
            ring_cges(data, arities, masks, mesh, spec,
                      GESConfig(max_q=64, counts_impl="fused",
                                family_cache=False),
                      return_cache_stats=True)
        except ValueError:
            pass
        else:
            raise AssertionError("expected ValueError")
        print("RING_CACHE_OK", rates)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "RING_CACHE_OK" in r.stdout
