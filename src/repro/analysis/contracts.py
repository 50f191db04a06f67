"""Pass 2 — jaxpr trace contracts over the REAL compiled programs.

The engine's correctness-by-construction claims ("one psum per count path",
"the whole GES loop compiles to one while_loop with a fixed carry", "no
re-traces in steady state") live only in docstrings until something walks
the jaxprs and checks them.  This pass traces the actual production
programs — ``sweep`` on all three backends, ``ges_jit_body``, the
restricted (W, n) ring program, ``fusion.fuse_trace`` and
``score_cache.lookup_or_compute`` — and verifies:

* **C001 collective-axis discipline** — every ``psum`` / ``ppermute`` /
  ``pmax`` / ``all_gather`` / ``axis_index`` equation names an axis the
  surrounding mesh declares; an unbound or misspelled axis name is a
  deploy-time crash on a bigger mesh.
* **C002 while-carry stability** — every ``lax.while_loop``'s carry avals
  are identical between loop input and body output (shape, dtype AND
  weak-type), so no promotion can leak through the compiled FES/BES loops.
* **C003 dtype discipline** — no float64/complex128 aval anywhere in the
  eqn graph (x64 creep silently doubles HBM traffic and breaks the
  all-f32 count-exactness argument).
* **C004 one-psum-per-count-path** — each count primitive under a data
  mesh axis (``local_score_masked`` per single backend,
  ``fused_insert_scores`` / ``fused_delete_scores`` per fused backend)
  contains EXACTLY one psum over that axis: zero means shard-local counts
  leak into the BDeu reduction, two means double-counted tables.
* **C005 steady-state re-trace pin** — running the jitted sweep / ges_jit
  / ring programs for several same-shape rounds must not grow their
  compilation caches (a re-trace at paper scale is minutes, not ms).

All checks run on tiny synthetic problems — the contracts are about the
trace/eqn structure, which is shape-generic.
"""
from __future__ import annotations

from functools import partial
from typing import Iterable, List, Optional, Set, Tuple

import numpy as np

from .findings import Finding

# Collectives whose axis names must be declared by the surrounding mesh.
COLLECTIVE_PRIMS = ("psum", "pmax", "pmin", "ppermute", "all_gather",
                    "all_to_all", "reduce_scatter", "axis_index", "pbroadcast")

FORBIDDEN_DTYPES = ("float64", "complex128")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: dict):
    """Every Jaxpr/ClosedJaxpr nested in an eqn's params (pjit bodies,
    while cond/body, cond branches, scan, shard_map, custom_* calls)."""
    import jax.extend.core as jcore
    out = []

    def visit(v):
        if isinstance(v, jcore.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jcore.Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for item in v:
                visit(item)

    for v in params.values():
        visit(v)
    return out


def iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and (recursively) its sub-jaxprs."""
    import jax.extend.core as jcore
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _eqn_axes(eqn) -> Tuple[str, ...]:
    """Named mesh axes an eqn's collective operates over."""
    axes = []
    for key in ("axes", "axis_name", "axis_index_groups_axis_name"):
        v = eqn.params.get(key)
        if v is None:
            continue
        for a in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(a, str):
                axes.append(a)
    return tuple(axes)


def collective_eqns(jaxpr):
    """[(prim_name, axes)] for every collective eqn in the graph."""
    return [(eqn.primitive.name, _eqn_axes(eqn))
            for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in COLLECTIVE_PRIMS]


def check_collective_axes(jaxpr, declared: Iterable[str],
                          program: str) -> List[Finding]:
    declared = set(declared)
    findings = []
    for prim, axes in collective_eqns(jaxpr):
        bad = [a for a in axes if a not in declared]
        if bad or not axes:
            findings.append(Finding(
                "C001", program, 0,
                f"collective `{prim}` names axis {bad or '<none>'} but the "
                f"mesh declares only {sorted(declared) or 'no axes'}"))
    return findings


def count_psums(jaxpr, axis: str) -> int:
    return sum(1 for prim, axes in collective_eqns(jaxpr)
               if prim == "psum" and axis in axes)


def check_while_carries(jaxpr, program: str) -> List[Finding]:
    """C002: while_loop carries fixed — body-out avals == carry-in avals,
    including weak_type (a weak carry re-traces or promotes downstream)."""
    findings = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "while":
            continue
        body = eqn.params["body_jaxpr"].jaxpr
        ncarry = len(body.outvars)
        carry_in = [v.aval for v in body.invars[-ncarry:]]
        carry_out = [v.aval for v in body.outvars]
        for i, (a_in, a_out) in enumerate(zip(carry_in, carry_out)):
            if a_in.shape != a_out.shape or a_in.dtype != a_out.dtype:
                findings.append(Finding(
                    "C002", program, 0,
                    f"while_loop carry[{i}] changes across the body: "
                    f"{a_in.str_short()} -> {a_out.str_short()}"))
            elif getattr(a_in, "weak_type", False) != \
                    getattr(a_out, "weak_type", False):
                findings.append(Finding(
                    "C002", program, 0,
                    f"while_loop carry[{i}] flips weak_type across the "
                    f"body ({a_in.str_short()} vs {a_out.str_short()}) — "
                    f"strengthen the init value (jnp.float32(...)/"
                    f"jnp.int32(...))"))
    return findings


def check_dtypes(jaxpr, program: str,
                 forbidden: Tuple[str, ...] = FORBIDDEN_DTYPES
                 ) -> List[Finding]:
    findings = []
    seen: Set[str] = set()
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in forbidden and (key := f"{eqn.primitive.name}:{dt}") \
                    not in seen:
                seen.add(key)
                findings.append(Finding(
                    "C003", program, 0,
                    f"{dt} aval flows through `{eqn.primitive.name}` — "
                    f"x64 creep; the engine is all-f32/int32 by contract"))
    return findings


# ---------------------------------------------------------------------------
# The real-program contract suite
# ---------------------------------------------------------------------------

def _tiny_problem(n: int = 6, m: int = 64, seed: int = 0):
    rng = np.random.default_rng(seed)
    arities = rng.integers(2, 4, size=n).astype(np.int32)
    data = (rng.integers(0, 10_000, size=(m, n)).astype(np.int32)
            % arities[None, :]).astype(np.int32)
    adj = np.zeros((n, n), dtype=np.int8)
    adj[0, 1] = adj[2, 1] = 1          # give delete sweeps real parents
    return data, arities, adj, int(arities.max())


def _structural_checks(jaxpr, program: str, declared=()) -> List[Finding]:
    return (check_collective_axes(jaxpr, declared, program)
            + check_while_carries(jaxpr, program)
            + check_dtypes(jaxpr, program))


def run_contract_checks(backends: Tuple[str, ...] = ("segment", "fused",
                                                     "fused_pallas"),
                        rounds: int = 3,
                        check_retrace: bool = True):
    """Trace the production programs and run every contract.

    Returns ``(findings, info)``; ``info`` records collective inventories,
    per-count-path psum counts and the retrace counters so the JSON report
    doubles as a contract snapshot.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from ..core import bdeu, fusion, score_cache
    from ..core.ges import GESConfig, ges_jit
    from ..core.ring import RingSpec, build_ring_program, ring_cges
    from ..core.sweeps import (sweep_column_body, sweep_matrix_body,
                               sweep_matrix_restricted_body)
    from ..core.partition import pid_table_from_allowed

    findings: List[Finding] = []
    info: dict = {"programs": {}, "count_paths": {}, "retrace": {}}

    data_np, arities_np, adj_np, r_max = _tiny_problem()
    n, m = adj_np.shape[0], data_np.shape[0]
    ess, max_q = 10.0, 64
    data = jnp.asarray(data_np)
    arities = jnp.asarray(arities_np)
    adj = jnp.asarray(adj_np)

    def record(name, jaxpr, declared=()):
        findings.extend(_structural_checks(jaxpr, name, declared))
        inv = {}
        for prim, axes in collective_eqns(jaxpr):
            key = f"{prim}[{','.join(axes)}]"
            inv[key] = inv.get(key, 0) + 1
        info["programs"][name] = inv

    # ---- sweep matrices on every backend (no mesh: zero collectives) -----
    for impl in backends:
        for kind in ("insert", "delete"):
            fn = partial(sweep_matrix_body, ess=ess, max_q=max_q,
                         r_max=r_max, counts_impl=impl, kind=kind)
            record(f"sweep[{impl},{kind}]",
                   jax.make_jaxpr(fn)(data, arities, adj))

    # ---- C004: one psum per count path under a data mesh axis -------------
    axis = "data"
    mesh = Mesh(np.array(jax.devices()[:1]), (axis,))

    def psum_count_of(fn, *args):
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=(P(axis, None),),
                               out_specs=P(), check_vma=False)
        return jax.make_jaxpr(mapped)(*args), None

    count_paths = {}
    for impl in ("segment", "onehot", "pallas"):
        def single(d, impl=impl):
            pm = adj.astype(bool)[:, 1]
            return bdeu.local_score_masked(d, arities, 1, pm, ess, max_q,
                                           r_max, impl, data_axis_name=axis)
        jx, _ = psum_count_of(single, data)
        count_paths[f"local_score[{impl}]"] = count_psums(jx, axis)
        findings.extend(check_collective_axes(jx, {axis},
                                              f"local_score[{impl}]"))
    for impl in ("fused", "fused_pallas"):
        for kind, prim_fn in (("insert", bdeu.fused_insert_scores),
                              ("delete", bdeu.fused_delete_scores)):
            def fused(d, impl=impl, prim_fn=prim_fn):
                pm = adj.astype(bool)[:, 1]
                return prim_fn(d, arities, 1, pm, ess, max_q, r_max, impl,
                               data_axis_name=axis)
            jx, _ = psum_count_of(fused, data)
            count_paths[f"{kind}_scores[{impl}]"] = count_psums(jx, axis)
            findings.extend(check_collective_axes(
                jx, {axis}, f"{kind}_scores[{impl}]"))
    info["count_paths"] = count_paths
    for name, cnt in count_paths.items():
        if cnt != 1:
            findings.append(Finding(
                "C004", name, 0,
                f"count path contains {cnt} psums over the data axis — the "
                f"additive-table contract requires EXACTLY one (0 leaks "
                f"shard-local counts into the BDeu reduction, >1 double-"
                f"counts)"))

    # ---- ges_jit_body: full-n, restricted and cached variants -------------
    allowed = jnp.asarray(np.ones((n, n), dtype=np.int8)
                          - np.eye(n, dtype=np.int8))
    pid_table = jnp.asarray(
        pid_table_from_allowed(np.asarray(allowed, dtype=bool)))
    from ..core.ges import ges_jit_body
    lim = jnp.int32(4)
    for name, kwargs in (
            ("ges_jit_body", {}),
            ("ges_jit_body[restricted]", {"pid_table": pid_table}),
            ("ges_jit_body[cached]", {"cache": score_cache.init(n, n, 64)})):
        def prog(d, a, g, al, kw=kwargs):
            return ges_jit_body(d, a, g, al, lim, ess, 4, max_q, r_max,
                                "segment", 1e-9, True, **kw)
        record(name, jax.make_jaxpr(prog)(data, arities, adj, allowed))

    # ---- the restricted (W, n) ring program -------------------------------
    ndev = len(jax.devices())
    k = 2 if ndev >= 2 else 1
    d_ax = 2 if ndev >= 2 * k else 1
    ring_axes = ("ring",) if d_ax == 1 else ("ring", "data")
    devs = np.array(jax.devices()[:k * d_ax]).reshape(
        (k,) if d_ax == 1 else (k, d_ax))
    ring_mesh = Mesh(devs, ring_axes)
    spec = RingSpec(k=k, max_rounds=3,
                    data_axis=None if d_ax == 1 else "data",
                    data_axis_size=d_ax)
    config = GESConfig(ess=ess, max_q=max_q, counts_impl="segment")
    prog = build_ring_program(ring_mesh, spec, config, r_max, add_limit=4,
                              restricted=True)
    edge_masks = np.stack([np.asarray(allowed, dtype=np.int8)] * k)
    init_g = np.zeros((k, n, n), dtype=np.int8)
    pid_tables = np.stack([np.asarray(pid_table)] * k)
    ring_args = (data, arities, jnp.asarray(edge_masks),
                 jnp.asarray(init_g), jnp.asarray(pid_tables))
    record(f"ring[{'x'.join(map(str, devs.shape))}]",
           jax.make_jaxpr(prog)(*ring_args), declared=set(ring_axes))

    # ---- fuse_trace and the family-score cache ----------------------------
    g2 = jnp.asarray(np.triu(np.ones((n, n), dtype=np.int8), 1))
    record("fuse_trace", jax.make_jaxpr(fusion.fuse_trace)(adj, g2))

    def cache_prog(d):
        cache = score_cache.init(n, n, 64)
        pm = adj.astype(bool)[:, 1]

        def compute():
            return sweep_column_body(d, arities, adj, 1, None, ess, max_q,
                                     r_max, "segment", "insert")
        col, cache = score_cache.lookup_or_compute(
            cache, score_cache.KIND_INSERT, 1, pm, 0, compute)
        return col, cache.hits
    record("score_cache.lookup_or_compute", jax.make_jaxpr(cache_prog)(data))

    # ---- C005: zero steady-state re-traces --------------------------------
    if check_retrace:
        retrace = {}

        # the compiled ring: one program object, `rounds` same-shape calls
        jax.block_until_ready(prog(*ring_args))
        base = prog._cache_size()
        for r in range(rounds):
            jax.block_until_ready(prog(*ring_args))
        retrace["ring"] = prog._cache_size() - base

        # ges_jit steady state (module-level jitted impl — measure growth
        # after the warm-up call, not absolute size)
        from ..core.ges import _ges_jit_impl
        cfg = GESConfig(ess=ess, max_q=max_q, counts_impl="segment")
        ges_jit(data, arities, adj, allowed, add_limit=4, config=cfg,
                r_max=r_max, pid_table=pid_table)
        base = _ges_jit_impl._cache_size()
        for r in range(rounds):
            d_r, *_ = _tiny_problem(seed=r + 1)
            ges_jit(jnp.asarray(d_r), arities, adj, allowed, add_limit=4,
                    config=cfg, r_max=r_max, pid_table=pid_table)
        retrace["ges_jit"] = _ges_jit_impl._cache_size() - base

        # the jitted sweep entry (matrix path)
        from ..core.sweeps import _sweep_matrix
        from ..core.sweeps import sweep as sweep_api
        sweep_api(data, arities, adj, kind="insert", ess=ess, max_q=max_q,
                  r_max=r_max, counts_impl="segment")
        base = _sweep_matrix._cache_size()
        for r in range(rounds):
            d_r, *_ = _tiny_problem(seed=r + 11)
            sweep_api(jnp.asarray(d_r), arities, adj, kind="insert",
                      ess=ess, max_q=max_q, r_max=r_max,
                      counts_impl="segment")
        retrace["sweep"] = _sweep_matrix._cache_size() - base

        info["retrace"] = retrace
        for name, extra in retrace.items():
            if extra:
                findings.append(Finding(
                    "C005", name, 0,
                    f"{extra} re-trace(s) across {rounds} steady-state "
                    f"same-shape rounds — the compilation cache must not "
                    f"grow after warm-up (weak types / non-hashable "
                    f"statics / python-scalar leaks are the usual cause)"))

    return findings, info
