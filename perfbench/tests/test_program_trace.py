"""The program's own spans and ``ges.steps`` counters (``program_trace.py``):
recorded on the CPU from a tiny cGES-L-4 job on one device and a tiny ring
job on four virtual devices, and reduced on hand-made events whose answers
are known."""
import gzip
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import program_trace as pt
from perfbench import tracing
from perfbench.tests import tiny
from perfbench.tracing import Device, Op, Trace

DATA = Path(__file__).resolve().parent / "data"

# Runs one traced job of ``cell`` through the harness's runner and the
# program's own entry points, and writes what the checks need to out.json.
_RECORD = """
import json
from pathlib import Path

import numpy as np
import jax.numpy as jnp

from perfbench import data, jobs, spec, tracing
from perfbench.tests import tiny
from repro.core import GESConfig, ges_jit, partition
from repro.core.cges import edge_add_limit
from repro.core.ring import RingSpec, build_ring_program, ring_cges

out = Path({out!r})
cell = spec.Benchmark(tiny.make_root(out)).cell({cell!r})
p = data.problem(cell.config, 11)
runner = jobs.Runner(cell.traffic, cell.config, p, tracing.Spans())
with tracing.capture(str(out / "trace")):
    answer = runner.run()
m, n = p.data.shape
k, r_max = int(cell.traffic["k"]), int(p.arities.max())
lim = edge_add_limit(n, k)
masks = np.asarray(answer.masks)
pids = partition.pid_tables(masks)
rec = {{"xplane": tracing.xplane_file(str(out / "trace")),
        "rounds": int(answer.rounds)}}
if runner.mesh is None:
    # round 0: each member's GES from the empty graph, called directly
    data_j = jnp.asarray(p.data.astype(np.int32))
    ar_j = jnp.asarray(p.arities.astype(np.int32))
    rec["round0"] = [
        [int(c) for c in ges_jit(
            data_j, ar_j, jnp.zeros((n, n), jnp.int8),
            jnp.asarray(masks[i].astype(np.int8)), add_limit=lim,
            config=runner.config, r_max=r_max, pid_table=pids[i])[2:4]]
        for i in range(k)]
else:
    spec_ = RingSpec(k=k, max_rounds=int(cell.config["max_rounds"]))
    prog = build_ring_program(runner.mesh, spec_, runner.config, r_max, lim,
                              restricted=True)
    got = prog(jnp.asarray(p.data.astype(np.int32)),
               jnp.asarray(p.arities.astype(np.int32)),
               jnp.asarray(masks.astype(np.int8)),
               jnp.zeros((k, n, n), jnp.int8), jnp.asarray(pids))
    again = ring_cges(p.data, p.arities, masks, runner.mesh, spec_,
                      runner.config, add_limit=lim)
    rec.update(
        n_outputs=len(got), steps=np.asarray(got[-1]).tolist(),
        returned=len(again),
        same_as_program=bool(
            np.array_equal(again[0], np.asarray(got[0]))
            and np.array_equal(again[1], np.asarray(got[1]))
            and again[2] == int(got[2])),
        same_as_job=bool(np.array_equal(again[0], answer.members)
                         and again[2] == answer.rounds))
(out / "out.json").write_text(json.dumps(rec))
"""


def _record(tmp, cell, devices):
    r = tiny.run_python(_RECORD.format(out=str(tmp), cell=cell),
                        devices=devices)
    assert r.returncode == 0, r.stderr[-3000:]
    rec = json.loads((tmp / "out.json").read_text())
    rec["trace"] = tracing.load_trace(rec["xplane"])
    rec["events"] = pt.load_program(rec["xplane"])
    return rec


@pytest.fixture(scope="module")
def cges_job(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("cges"), "pigs-cges-l4", 1)


@pytest.fixture(scope="module")
def ring_job(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("ring"), "pigs-ring4", 4)


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_every_program_span_and_the_counter_are_recorded(cges_job, ring_job):
    names = {e[0] for e in cges_job["events"] + ring_job["events"]}
    assert names == set(pt.PROGRAM_SPANS + pt.COUNTERS)
    assert not {e[0] for e in ring_job["events"]} & {
        "cges.round", "cges.fusion", "cges.member", "cges.finetune"}


def test_cges_spans_nest_inside_rounds_and_the_harness_span(cges_job):
    ev, rounds = cges_job["events"], cges_job["rounds"]
    harness = cges_job["trace"].span_intervals("cges")
    assert len(harness) == 1
    round_spans = {st["round"]: (s, e) for n, s, e, st in ev
                   if n == "cges.round"}
    assert sorted(round_spans) == list(range(rounds))
    assert all(_inside(r, harness[0]) for r in round_spans.values())
    members = [(st, (s, e)) for n, s, e, st in ev if n == "cges.member"]
    fusions = [(st, (s, e)) for n, s, e, st in ev if n == "cges.fusion"]
    assert len(members) == 4 * rounds and len(fusions) == 4 * (rounds - 1)
    for st, iv in members + fusions:
        assert _inside(iv, round_spans[st["round"]])
    (ft,) = pt.spans(ev, "cges.finetune")
    assert _inside(ft, harness[0]) and ft[0] >= max(
        e for _, e in round_spans.values())
    (part,) = cges_job["trace"].span_intervals("partition")
    for name in ("partition.similarity", "partition.clusters"):
        assert all(_inside(iv, part) for iv in pt.spans(ev, name))


def test_ring_spans_run_in_order_inside_the_harness_span(ring_job):
    ev = ring_job["events"]
    (ring,) = ring_job["trace"].span_intervals("ring")
    (build,), (launch,), (run,) = (pt.spans(ev, n) for n in
                                   ("ring.build", "ring.launch", "ring.run"))
    assert build[1] <= launch[0] and launch[1] <= run[0]
    assert all(_inside(iv, ring) for iv in (build, launch, run))
    (stats,) = [st for n, _, _, st in ev if n == "ring.run"]
    assert stats == {"rounds": ring_job["rounds"]}
    assert pt.coverage(ev, ring, ("ring.build", "ring.launch",
                                  "ring.run")) > 0.9


def test_ring_counters_are_the_program_output(ring_job):
    steps = np.asarray(ring_job["steps"])          # (k, max_rounds, 2)
    rounds = ring_job["rounds"]
    assert ring_job["n_outputs"] == 4
    assert not steps[:, rounds:].any() and steps[:, :rounds].any()
    want = {(r, i): tuple(steps[i, r]) for i in range(4)
            for r in range(rounds)}
    assert pt.steps(ring_job["events"]) == want


def test_ring_cges_returns_the_same_three_values(ring_job):
    assert ring_job["returned"] == 3
    assert ring_job["same_as_program"] and ring_job["same_as_job"]


def test_cges_and_ring_counters_agree(cges_job, ring_job):
    assert cges_job["rounds"] == ring_job["rounds"]
    got = pt.steps(cges_job["events"])
    assert got == pt.steps(ring_job["events"])
    assert [list(got[0, i]) for i in range(4)] == cges_job["round0"]
    assert sum(i + d for i, d in got.values()) > 0


# ---------------------------------------------------------------------------
# Hand-made events
# ---------------------------------------------------------------------------

def _steps(r, i, ins, dels):
    return ("ges.steps", 0, 0, {"round": r, "member": i, "inserts": ins,
                                "deletes": dels})


def synthetic():
    """A one-chip job: stage 1 0-3 s, two rounds of 2 and 4 s, a 1 s
    fine-tune; two ring launches of 5 s in all."""
    s = 10 ** 9
    return [
        ("partition.similarity", 0, 2 * s, {}),
        ("partition.clusters", 2 * s, 3 * s, {}),
        ("cges.round", 3 * s, 5 * s, {"round": 0}),
        ("cges.member", 3 * s, 4 * s, {"round": 0, "member": 0}),
        ("cges.round", 5 * s, 9 * s, {"round": 1}),
        ("cges.finetune", 9 * s, 10 * s, {}),
        ("ring.launch", 10 * s, 12 * s, {}),
        ("ring.launch", 12 * s, 15 * s, {}),
        # round 0: steps 4, 2, 0 (excess 3*4 - 6 = 6); round 1: 1, 1, 1
        _steps(0, 0, 3, 1), _steps(0, 1, 2, 0), _steps(0, 2, 0, 0),
        _steps(1, 0, 1, 0), _steps(1, 1, 0, 1), _steps(1, 2, 1, 0),
    ]


READERS = {"partition_similarity_s": 2.0, "cges_round_s": 3.0,
           "cges_finetune_s": 1.0, "ring_launch_s": 5.0,
           "ring_lockstep_excess": 100.0 * 6 / 9}


@pytest.mark.parametrize("name", sorted(READERS))
def test_synthetic_readers(name):
    assert getattr(pt, name)(synthetic()) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_are_none_without_their_events(name):
    assert getattr(pt, name)([]) is None


@pytest.mark.parametrize("fixture", ["tiny_ges.xplane.pb",
                                     "tiny_ring.xplane.pb.gz"])
def test_readers_are_none_on_traces_without_program_spans(fixture, tmp_path):
    path = DATA / fixture
    if fixture.endswith(".gz"):
        path = tmp_path / fixture[:-3]
        with gzip.open(DATA / fixture) as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    events = pt.load_program(str(path))
    assert events == []
    assert all(getattr(pt, name)(events) is None for name in READERS)


def test_lockstep_excess_is_none_when_no_member_stepped():
    assert pt.ring_lockstep_excess([_steps(0, i, 0, 0)
                                    for i in range(4)]) is None
    assert pt.ring_lockstep_excess([_steps(0, i, 2, 0)
                                    for i in range(4)]) == 0.0


def test_coverage_counts_overlapping_spans_once():
    ev = [("ring.build", 0, 4, {}), ("ring.launch", 2, 6, {}),
          ("ring.run", 8, 12, {}), ("cges.round", 0, 10, {})]
    names = ("ring.build", "ring.launch", "ring.run")
    assert pt.coverage(ev, (0, 10), names) == pytest.approx(0.8)
    assert pt.coverage(ev, (0, 10), ("ring.run",)) == pytest.approx(0.2)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    dev = Device("/device:TPU:0", [Op("fusion", 0, 1), Op("fusion", 3, 5),
                                   Op("fusion", 7, 9), Op("fusion", 11, 13),
                                   Op("fusion", 15, 20)])
    trace = Trace([dev], [("job", 0, 20), ("ring", 4, 20)], (0, 20))
    ev = [("ring.build", 4, 8, {}), ("ring.launch", 8, 12, {}),
          ("ring.run", 12, 20, {}),
          ("ges.steps", 14, 14, {"round": 0, "member": 0, "inserts": 1,
                                 "deletes": 0})]
    gaps = dict(pt.idle_gaps(trace, ev))
    assert gaps == pytest.approx({"job": 2e-9, "ring.build": 2e-9,
                                  "ring.launch": 2e-9, "ring.run": 2e-9})
    # the harness's own naming is unchanged
    assert dict(tracing.idle_gaps(trace)) == pytest.approx(
        {"job": 2e-9, "ring": 6e-9})
