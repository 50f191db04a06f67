"""Host spans, compile time, and the reduction of a profiler trace.

Spans are the benchmark's own: ``span(name)`` times a call into the program
on the host clock and writes a ``TraceAnnotation`` into the profiler's trace,
so the trace's idle gaps can be named by what the host was doing.

``load_trace`` reads one ``.xplane.pb`` with nothing but JAX.  On a TPU each
chip is a plane ``/device:TPU:<i>`` whose ``XLA Ops`` line holds one event
per executed HLO op, named by the op's HLO text (``%bdeu_sweep_insert.15 =
f32[...] custom-call(...)``); a ``while`` op's event spans its body's ops.
The host's annotations are events of the ``/host:CPU`` plane.  All events
share one clock (ns).
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import time
from collections import defaultdict

SPANS = ("job", "partition", "cges", "ring", "finetune", "ges")
# Ops whose event spans other ops' events: counted in busy time (the union),
# never as an op of their own.
CONTAINERS = ("while", "conditional", "call")
KERNELS = ("bdeu_sweep_insert", "bdeu_sweep_delete", "bdeu_count")
# Collectives by HLO opcode, or by the JAX primitive that names the op (a
# per-round ``pmax`` runs as an op named ``pmax.3``).
_COLLECTIVE = re.compile(r"\b(collective-permute|all-reduce|all-gather|"
                         r"reduce-scatter|all-to-all)(-start|-done)?\(")
COLLECTIVE_PRIMITIVES = ("pmax", "pmin", "psum", "ppermute", "all_gather",
                         "all_to_all", "psum_scatter")


class Spans:
    """Seconds per span name on the host clock, and of those the seconds
    ``clock`` (a ``CompileClock``) counted, per span name."""

    def __init__(self, clock=None):
        self.seconds = defaultdict(float)
        self.compile = defaultdict(float)
        self.clock = clock

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            c0 = self.clock.seconds if self.clock else 0.0
            try:
                yield
            finally:
                self.seconds[name] += time.perf_counter() - t0
                if self.clock:
                    self.compile[name] += self.clock.seconds - c0


class GcClock:
    """Seconds the Python garbage collector has run, from its callbacks."""

    def __init__(self):
        import gc

        self.seconds = 0.0
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, _info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


# ---------------------------------------------------------------------------
# Trace capture
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the body into ``log_dir``; Python function tracing is off."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(log_dir: str):
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return files[-1] if files else None


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Op:
    name: str          # HLO op name without its number: "bdeu_sweep_insert"
    start: float       # ns
    end: float
    collective: bool = False


@dataclasses.dataclass
class Device:
    name: str
    ops: list          # every XLA op event, containers included

    def leaf_ops(self):
        return [o for o in self.ops if o.name not in CONTAINERS]

    def busy(self, lo: float, hi: float) -> list:
        """Merged intervals in which some op ran, clipped to [lo, hi]."""
        out = []
        for o in sorted(self.ops, key=lambda o: o.start):
            s, e = max(o.start, lo), min(o.end, hi)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out


@dataclasses.dataclass
class Trace:
    devices: list      # [Device], one per chip
    spans: list        # [(name, start ns, end ns)] of the harness's spans
    window: tuple      # (start ns, end ns) of the traced jobs

    def span_intervals(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == name]


_OP_NAME = re.compile(r"^%?([^ =]+)")


def op_base(hlo_text: str) -> str:
    """'%bdeu_sweep_insert.15 = f32[...] ...' -> 'bdeu_sweep_insert'."""
    m = _OP_NAME.match(hlo_text.strip())
    name = m.group(1) if m else hlo_text
    return re.sub(r"\.\d+$", "", name)


def load_trace(path: str, chips: int | None = None) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append(Op(op_base(e.name), e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  is_collective(e.name)))
            devices.append(Device(plane.name, ops))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    if chips is not None:
        devices = devices[:chips]
    jobs = [(s, e) for n, s, e in spans if n == "job"]
    if jobs:
        window = (min(s for s, _ in jobs), max(e for _, e in jobs))
    else:
        ends = [(o.start, o.end) for d in devices for o in d.ops]
        window = ((min(s for s, _ in ends), max(e for _, e in ends))
                  if ends else (0.0, 0.0))
    return Trace(devices, spans, window)


def _overlap(intervals, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def busy_seconds(trace: Trace) -> list:
    """Busy seconds of each chip inside the traced window."""
    lo, hi = trace.window
    return [_overlap(d.busy(lo, hi), lo, hi) / 1e9 for d in trace.devices]


def window_seconds(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def op_seconds(device: Device, match, lo=None, hi=None) -> float:
    """Summed seconds of the leaf ops that ``match`` accepts, inside
    [lo, hi] (the traced window by default)."""
    total = 0.0
    for o in device.leaf_ops():
        if match(o):
            s = o.start if lo is None else max(o.start, lo)
            e = o.end if hi is None else min(o.end, hi)
            total += max(0.0, e - s)
    return total / 1e9


def op_count(device: Device, name: str) -> int:
    return sum(1 for o in device.ops if o.name == name)


def is_collective(hlo_text: str) -> bool:
    """'%pmax.3 = f32[] all-reduce(f32[] %x), ...' is a collective."""
    return (_COLLECTIVE.search(hlo_text) is not None
            or op_base(hlo_text) in COLLECTIVE_PRIMITIVES)


def top_ops(trace: Trace, k: int = 10) -> list:
    """[(op, seconds)] of the leaf ops that took most device time, averaged
    over the chips."""
    tot = defaultdict(float)
    lo, hi = trace.window
    for d in trace.devices:
        for o in d.leaf_ops():
            tot[o.name] += max(0.0, min(o.end, hi) - max(o.start, lo)) / 1e9
    n = max(1, len(trace.devices))
    return sorted(([name, s / n] for name, s in tot.items()),
                  key=lambda x: -x[1])[:k]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """[(span, seconds)]: the device's idle time inside the traced window,
    named by the innermost harness span that covers each gap's midpoint
    ('other' where none does), averaged over the chips."""
    tot = defaultdict(float)
    lo, hi = trace.window
    for d in trace.devices:
        t = lo
        for s, e in d.busy(lo, hi) + [[hi, hi]]:
            if s > t:
                mid = 0.5 * (s + t)
                cover = [(se - ss, n) for n, ss, se in trace.spans
                         if ss <= mid <= se]
                tot[min(cover)[1] if cover else "other"] += (s - t) / 1e9
            t = max(t, e)
    n = max(1, len(trace.devices))
    return sorted(([name, s / n] for name, s in tot.items()),
                  key=lambda x: -x[1])[:k]
