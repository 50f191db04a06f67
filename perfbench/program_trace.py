"""The program's own spans and step counters in a profiler trace, and the
per-layer readings they give.

The program (``src/repro``) writes ``jax.profiler.TraceAnnotation`` events
into the profiler's trace, on the host plane of the same ``.xplane.pb`` as
the device ops and on the same clock (ns):

* spans (``PROGRAM_SPANS``): ``partition.similarity`` and
  ``partition.clusters`` (stage 1); ``cges.round`` (stat ``round``), with
  ``cges.fusion`` and ``cges.member`` (stats ``round``, ``member``) inside
  it, and ``cges.finetune`` (the one-chip round loop and fine-tune);
  ``ring.build``, ``ring.launch`` and ``ring.run`` (stat ``rounds``) inside
  ``ring.ring_cges``;
* the counter ``ges.steps``: one instant event per ring member per round,
  with stats ``round``, ``member``, ``inserts`` and ``deletes``.

``load_program(path)`` keeps these events as ``[(name, start_ns, end_ns,
stats)]``; the reading functions take that list and return ``None``, never
0, when what they read is absent.  ``idle_gaps`` names the chip's idle time
by the innermost span among the harness's spans and these.
"""
from __future__ import annotations

from collections import defaultdict

from perfbench import tracing

PROGRAM_SPANS = ("partition.similarity", "partition.clusters",
                 "cges.round", "cges.fusion", "cges.member", "cges.finetune",
                 "ring.build", "ring.launch", "ring.run")
COUNTERS = ("ges.steps",)


def load_program(path: str) -> list:
    """[(name, start ns, end ns, stats)] of the program's spans and counters
    on the host plane of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    keep = PROGRAM_SPANS + COUNTERS
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in keep:
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda e: e[1])


def spans(events: list, name: str) -> list:
    """[(start ns, end ns)] of the events named ``name``."""
    return [(s, e) for n, s, e, _ in events if n == name]


def _summed_s(events, name):
    t = spans(events, name)
    return sum(e - s for s, e in t) / 1e9 if t else None


def partition_similarity_s(events: list):
    """Seconds of stage 1's pairwise similarity matrix."""
    return _summed_s(events, "partition.similarity")


def cges_round_s(events: list):
    """Mean seconds of one lockstep round of ``cges``."""
    t = spans(events, "cges.round")
    return sum(e - s for s, e in t) / len(t) / 1e9 if t else None


def cges_finetune_s(events: list):
    """Seconds of the unrestricted fine-tune of ``cges``."""
    return _summed_s(events, "cges.finetune")


def ring_launch_s(events: list):
    """Seconds of the ring program's trace, compile or cache load and
    enqueue."""
    return _summed_s(events, "ring.launch")


def steps(events: list) -> dict:
    """{(round, member): (inserts, deletes)} of the ``ges.steps`` counters."""
    return {(st["round"], st["member"]): (st["inserts"], st["deletes"])
            for n, _, _, st in events if n == "ges.steps"}


def ring_lockstep_excess(events: list):
    """Steps the lockstep rounds wait for, over the steps taken, in %:
    100 * sum_r (k * max_i s_ir - sum_i s_ir) / sum_r sum_i s_ir, with
    s_ir the inserts and deletes of member i in round r."""
    rounds = defaultdict(list)
    for (r, _), (ins, dels) in steps(events).items():
        rounds[r].append(ins + dels)
    done = sum(sum(s) for s in rounds.values())
    if done <= 0:
        return None
    wait = sum(len(s) * max(s) - sum(s) for s in rounds.values())
    return 100.0 * wait / done


def coverage(events: list, outer: tuple, names) -> float:
    """Share of the interval ``outer`` (start ns, end ns) that the union of
    the spans named in ``names`` covers."""
    lo, hi = outer
    inner = sorted((max(s, lo), min(e, hi)) for n, s, e, _ in events
                   if n in names and min(e, hi) > max(s, lo))
    covered, t = 0.0, lo
    for s, e in inner:
        covered += max(0.0, e - max(s, t))
        t = max(t, e)
    return covered / (hi - lo) if hi > lo else 0.0


def idle_gaps(trace: tracing.Trace, events: list, k: int = 10) -> list:
    """[(span, seconds)]: as ``tracing.idle_gaps``, with each gap named by
    the innermost span among the harness's spans and the program's
    spans (not its instant counters) that covers its midpoint."""
    named = list(trace.spans) + [(n, s, e) for n, s, e, _ in events
                                 if n in PROGRAM_SPANS]
    return tracing.idle_gaps(tracing.Trace(trace.devices, named,
                                           trace.window), k)
