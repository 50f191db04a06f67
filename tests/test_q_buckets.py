"""The ``ges.q_buckets`` counter: column reductions per row bucket of the
fused BDeu reduction (``bdeu.q_ladder``), written per ring member and round
and for the fine-tune.  A tiny cGES-L-4 job is recorded under the profiler
on one CPU device (``cges(engine="jax")``) and on the ring over 4 virtual
devices (``ring_cges``), and recounted on the host from the parent sets the
host engine's GES steps through (subprocess: forced host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import bdeu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_JOB = textwrap.dedent("""
    import glob, json, os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, "src")
    import importlib
    import numpy as np, jax
    from jax.profiler import ProfileData
    from jax.sharding import Mesh
    from repro.core import GESConfig, edge_add_limit, partition
    from repro.core.ring import RingSpec, ring_cges
    from repro.data.bn import forward_sample, random_bn

    cges_mod = importlib.import_module("repro.core.cges")
    ges_mod = importlib.import_module("repro.core.ges")

    rng = np.random.default_rng(4)
    bn = random_bn(rng, n=12, n_edges=18, max_parents=3,
                   arity_choices=(3, 4))
    data = forward_sample(bn, 600, rng)
    n, k, rounds_cap = bn.n, 4, 3
    cfg = GESConfig(max_q=1024, counts_impl="fused")
    masks = partition.partition_edges(data, bn.arities, k)

    def events(fn):
        '''[(name, stats)] of the counters written while fn runs.'''
        d = tempfile.mkdtemp()
        with jax.profiler.trace(d):
            fn()
        path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))[0]
        return [(e.name, {s: v for s, v in e.stats})
                for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU" for line in p.lines
                for e in line.events
                if e.name in ("ges.steps", "ges.q_buckets")]

    def cges_jax():
        cges_mod.cges(data, bn.arities, k=k, limit=True, config=cfg,
                      engine="jax", max_rounds=rounds_cap, edge_masks=masks)

    def ring():
        mesh = Mesh(np.array(jax.devices()[:k]), ("ring",))
        ring_cges(data, bn.arities, masks, mesh,
                  RingSpec(k=k, max_rounds=rounds_cap), cfg,
                  add_limit=edge_add_limit(n, k))

    # the host engine, recording each GES call and every column it asks for
    calls, real_host, real_column = [], cges_mod.ges_host, \\
        ges_mod.ScoreCache.column

    def column(self, kind, y, adj, compute, scope=b""):
        calls[-1]["cols"].append((kind, int(y), adj[:, y].tolist()))
        return real_column(self, kind, y, adj, compute, scope=scope)

    def ges_host(data_, arities, init_adj=None, **kw):
        calls.append({"init": np.asarray(init_adj).tolist(), "cols": []})
        res = real_host(data_, arities, init_adj=init_adj, **kw)
        calls[-1].update(adj=res.adj.tolist(), ins=res.n_inserts,
                         dels=res.n_deletes)
        return res

    cges_mod.ges_host, ges_mod.ScoreCache.column = ges_host, column
    host = cges_mod.cges(data, bn.arities, k=k, limit=True, config=cfg,
                         engine="host", max_rounds=rounds_cap,
                         edge_masks=masks)
    print(json.dumps({"cges": events(cges_jax), "ring": events(ring),
                      "host_calls": calls, "host_rounds": host.rounds,
                      "arities": bn.arities.tolist(), "n": n, "k": k}))
""")


def _bucket(arities, parents, ladder):
    q = int(np.prod(np.asarray(arities)[np.asarray(parents, bool)]))
    return next((i for i, rows in enumerate(ladder) if q <= rows),
                len(ladder) - 1)


def _recount(call, arities, ladder):
    """Histogram of one GES call from its parent sets: every child of the
    start graph (FES's matrix), one column per insertion, every child of the
    graph FES ends at (BES's matrix), one column per deletion."""
    init = np.asarray(call["init"], dtype=np.int8)
    hist = np.zeros(len(ladder), dtype=int)

    def add_all(adj):
        for y in range(adj.shape[0]):
            hist[_bucket(arities, adj[:, y], ladder)] += 1

    def steps(kind, count):
        cols = [(y, pa) for kd, y, pa in call["cols"] if kd == kind]
        return cols[len(cols) - count:] if count else []

    add_all(init)
    adj = init.copy()
    for y, pa in steps("ins", call["ins"]):
        adj[:, y] = pa
        hist[_bucket(arities, pa, ladder)] += 1
    add_all(adj)
    for y, pa in steps("del", call["dels"]):
        adj[:, y] = pa
        hist[_bucket(arities, pa, ladder)] += 1
    assert np.array_equal(adj, np.asarray(call["adj"]))   # steps replayed
    return hist


@pytest.fixture(scope="module")
def job():
    r = subprocess.run([sys.executable, "-c", _JOB], capture_output=True,
                       text=True, timeout=900, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-4000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    ladder = bdeu.q_ladder(1024)

    def by_key(evs, name):
        return {(st["round"], st["member"]): st for nm, st in evs
                if nm == name}

    for side in ("cges", "ring"):
        rec[side + "_q"] = {key: [st[f"q{rows}"] for rows in ladder]
                            for key, st in by_key(rec[side],
                                                  "ges.q_buckets").items()}
        rec[side + "_steps"] = {
            key: st["inserts"] + st["deletes"]
            for key, st in by_key(rec[side], "ges.steps").items()}
    return rec


def test_q_buckets_equal_a_host_recount(job):
    """Every member's histogram, and the fine-tune's (member -1), is the
    bucket count of the parent sets its GES stepped through."""
    ladder = bdeu.q_ladder(1024)
    k, rounds = job["k"], job["host_rounds"]
    calls = job["host_calls"]
    assert len(calls) == rounds * k + 1
    keys = [(r, i) for r in range(rounds) for i in range(k)]
    keys.append((rounds, -1))
    want = {key: _recount(c, job["arities"], ladder).tolist()
            for key, c in zip(keys, calls)}
    assert job["cges_q"] == want
    used = np.flatnonzero(np.sum(list(want.values()), axis=0))
    assert len(used) >= 2, used        # the job reaches more than one bucket


def test_q_buckets_same_on_one_device_and_the_ring(job):
    members = {key: v for key, v in job["cges_q"].items() if key[1] >= 0}
    assert members and job["ring_q"] == members
    assert job["ring_steps"] == job["cges_steps"]


def test_q_buckets_sum_to_the_columns_the_steps_imply(job):
    """Two n-column matrix sweeps per GES call plus one column per step."""
    n = job["n"]
    for side in ("cges", "ring"):
        for key, steps in job[side + "_steps"].items():
            assert sum(job[side + "_q"][key]) == 2 * n + steps, (side, key)
    finetune = job["host_calls"][-1]
    (ft_key,) = [key for key in job["cges_q"] if key[1] == -1]
    assert sum(job["cges_q"][ft_key]) == (
        2 * n + finetune["ins"] + finetune["dels"])
