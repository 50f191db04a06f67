"""Drives whole runs of one tiny cell on the CPU, sound and with the timed
path broken underneath, and prints what ``correct`` came out as.

    python -c "from perfbench.tests import faults; faults.main(cell, root)"

Each fault is planted in the program (or, for ``control_bf16``, the program
is replaced by the reference scoring in bfloat16) only while its run lasts:

* ``state_unchanged``: every GES call returns the graph it started from.
* ``half_batch``: every learning call sees the first half of the instances.
* ``no_exchange``: ring members never receive their predecessor's graph
  (cGES: fusion keeps the member's own graph; the ring: ``ppermute`` is the
  identity).
* ``answer_altered``: every GES call drops one edge of the graph it
  returns, keeping the score it reported.
* ``members_stop_early``: ring members stop FES at a quarter of the cGES-L
  insertion limit.
* ``wrong_winner``: cGES fine-tunes from the worst member of the last
  improving round instead of the best (on the ring the harness, not the
  program, picks the winner).
* ``control_bf16``: the reference learner in bfloat16 answers instead
  (``control.planted``).
"""
from __future__ import annotations

import contextlib
import io
import json
from pathlib import Path

import numpy as np

FAULTS = {
    "cges": ("state_unchanged", "half_batch", "no_exchange", "answer_altered",
             "members_stop_early", "wrong_winner", "control_bf16"),
    "ring_cges": ("state_unchanged", "half_batch", "no_exchange",
                  "answer_altered", "members_stop_early", "control_bf16"),
    "ges": ("state_unchanged", "half_batch", "answer_altered",
            "control_bf16"),
}


@contextlib.contextmanager
def _setattrs(pairs):
    old = [(obj, name, getattr(obj, name)) for obj, name, _ in pairs]
    try:
        for obj, name, value in pairs:
            setattr(obj, name, value)
        yield
    finally:
        for obj, name, value in old:
            setattr(obj, name, value)


def _wrap_ges_jit(change):
    """Patch every name under which the harness and cges reach ges_jit."""
    import importlib

    core = importlib.import_module("repro.core")
    cges_mod = importlib.import_module("repro.core.cges")
    ges_mod = importlib.import_module("repro.core.ges")

    real = ges_mod.ges_jit

    def fake(data, arities, init_adj, allowed, *args, **kw):
        return change(real, data, arities, init_adj, allowed, args, kw)

    return _setattrs([(core, "ges_jit", fake), (cges_mod, "ges_jit", fake),
                      (ges_mod, "ges_jit", fake)])


def _unchanged(real, data, arities, init_adj, allowed, args, kw):
    out = real(data, arities, init_adj, allowed, *args, **kw)
    return (init_adj,) + tuple(out[1:])


def _half(real, data, arities, init_adj, allowed, args, kw):
    return real(data[: data.shape[0] // 2], arities, init_adj, allowed,
                *args, **kw)


def _drop_edge(real, data, arities, init_adj, allowed, args, kw):
    import jax.numpy as jnp

    out = real(data, arities, init_adj, allowed, *args, **kw)
    adj = np.asarray(out[0]).copy()
    if adj.any():
        adj.flat[int(np.flatnonzero(adj)[0])] = 0
    return (jnp.asarray(adj),) + tuple(out[1:])


def _early(real, data, arities, init_adj, allowed, args, kw):
    if kw.get("add_limit") is not None:
        kw = dict(kw, add_limit=max(1, kw["add_limit"] // 4))
    return real(data, arities, init_adj, allowed, *args, **kw)


class _ArgmaxIsArgmin:
    """numpy, with ``argmax`` answering the position of the least value."""

    argmax = staticmethod(np.argmin)

    def __getattr__(self, name):
        return getattr(np, name)


@contextlib.contextmanager
def plant(fault: str):
    import importlib

    import jax

    fusion = importlib.import_module("repro.core.fusion")
    ring = importlib.import_module("repro.core.ring")

    if fault == "state_unchanged":
        real_body = ring.ges_jit_body

        def body(data, arities, init_adj, *args, **kw):
            out = real_body(data, arities, init_adj, *args, **kw)
            return (init_adj,) + tuple(out[1:])

        with _wrap_ges_jit(_unchanged), _setattrs(
                [(ring, "ges_jit_body", body)]):
            yield
    elif fault == "half_batch":
        real_ring = ring.ring_cges

        def ring_half(data, *args, **kw):
            return real_ring(data[: data.shape[0] // 2], *args, **kw)

        with _wrap_ges_jit(_half), _setattrs([(ring, "ring_cges", ring_half)]):
            yield
    elif fault == "no_exchange":
        with _setattrs([
                (fusion, "fusion_edge_union",
                 lambda own, pred, engine=None: np.asarray(own)),
                (jax.lax, "ppermute", lambda x, axis_name, perm: x)]):
            yield
    elif fault == "answer_altered":
        with _wrap_ges_jit(_drop_edge):
            yield
    elif fault == "members_stop_early":
        real_ring = ring.ring_cges

        def ring_early(*args, add_limit=None, **kw):
            return real_ring(*args, add_limit=max(1, add_limit // 4), **kw)

        with _wrap_ges_jit(_early), _setattrs(
                [(ring, "ring_cges", ring_early)]):
            yield
    elif fault == "wrong_winner":
        cges_mod = importlib.import_module("repro.core.cges")
        with _setattrs([(cges_mod, "np", _ArgmaxIsArgmin())]):
            yield
    elif fault == "control_bf16":
        from perfbench import control

        with control.planted():
            yield
    else:
        raise ValueError(f"unknown fault {fault!r}")


def run_cell(cell: str, root: Path, seed: int, trace: int = 0) -> dict:
    from perfbench.harness import main

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.1", "--trace", str(trace)], root=root,
                  require_tpu=False)
    lines = out.getvalue().strip().splitlines()
    return {"rc": rc, "result": json.loads(lines[-1]) if lines else None,
            "stderr_tail": err.getvalue()[-2000:]}


def main(cell: str, root: str, seed: int = 11, trace: int = 0) -> None:
    from perfbench import spec

    root = Path(root)
    algo = spec.Benchmark(root).cell(cell).traffic["algorithm"]
    report = {"sound": run_cell(cell, root, seed, trace)}
    for fault in FAULTS[algo]:
        with plant(fault):
            report[fault] = run_cell(cell, root, seed)
    print(json.dumps(report))
