"""One run of one cell: set-up, a measured window of whole jobs, the
correctness check, and the result line.

Set-up draws the cell's dataset from ``--seed`` and learns one DAG from it
(the warm-up job), so every program the window runs is compiled or loaded
from the persistent compilation cache at ``<checkout>/.jax_cache``; it ends
with a full garbage collection, so the window does not collect what set-up
left.  The window then learns DAGs from that dataset back to back until
``--seconds`` have passed; a job that has started runs to its end.
``dag_s`` is the window's time over the jobs it completed.  Each job's
record in the result's ``jobs`` holds its spans, the seconds of
compilation, garbage collection and process CPU time inside it, and the
process's page faults and context switches in it.

With ``--trace 1`` the first job of the window is profiled and the line
carries the cell's per-layer metrics instead of its end-to-end ones.

After the window one job, drawn from the seed, is judged against the plain
reference (``checks.py``), and every other job must have returned the same
DAG and score.  For cGES the round before the judged members' is learned
again first (``Runner.previous_round``), outside the window.  Each compared
number is printed beside its limit as the last lines of stderr and under
``checks``, the last key of the result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import resource
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import checks, data, jobs, roofline, spec, tracing

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class JobRecord:
    wall_s: float
    spans: dict
    span_compile: dict
    compile_s: float
    gc_s: float
    cpu_s: float
    switches: dict
    rounds: int | None
    traced: bool


def parse(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell: seconds per learned DAG.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def _use_compile_cache(root: Path) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


_USAGE = ("ru_majflt", "ru_minflt", "ru_nvcsw", "ru_nivcsw")


def _usage() -> dict:
    """Page faults and context switches of this process so far."""
    u = resource.getrusage(resource.RUSAGE_SELF)
    return {k[3:]: getattr(u, k) for k in _USAGE}


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def _value(v, unit):
    if v is None:
        return None
    out = dict(v) if isinstance(v, dict) else {"value": v}
    out["value"] = float(out["value"])
    out["unit"] = unit
    return out


def main(argv=None, *, root=None, require_tpu=True, t0=None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    root = Path(root) if root else ROOT
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    bench = spec.Benchmark(root)
    cell = bench.cell(args.workload)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        print(f"no TPU: JAX found {platform} devices; this benchmark "
              f"measures the chip only", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    peaks = roofline.peaks(used[0].device_kind) if platform == "tpu" else None
    _use_compile_cache(root)

    clock, gc_clock = tracing.CompileClock(), tracing.GcClock()
    problem = data.problem(cell.config, args.seed)
    runner = jobs.Runner(cell.traffic, cell.config, problem, tracing.Spans())
    warm = runner.run()
    gc.collect()
    setup_s = time.perf_counter() - t0

    # ---- the measured window -------------------------------------------
    records, answers, raised = [], [], 0
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if args.trace \
        else None
    w0 = time.perf_counter()
    while True:
        runner.spans = tracing.Spans(clock)
        traced = bool(args.trace) and not records
        c0, g0, p0 = clock.seconds, gc_clock.seconds, time.process_time()
        u0 = _usage()
        j0 = time.perf_counter()
        try:
            if traced:
                with tracing.capture(trace_dir):
                    answer = runner.run()
            else:
                answer = runner.run()
        except Exception:                    # a job that fails is counted
            traceback.print_exc()
            answer = None
            raised += 1
        j1 = time.perf_counter()
        records.append(JobRecord(
            j1 - j0, dict(runner.spans.seconds),
            dict(runner.spans.compile), clock.seconds - c0,
            gc_clock.seconds - g0, time.process_time() - p0,
            {k: v - u0[k] for k, v in _usage().items()},
            getattr(answer, "rounds", None), traced))
        answers.append(answer)
        if j1 - w0 >= args.seconds:
            break
    window_s = time.perf_counter() - w0
    peak_bytes = _peak_bytes(used)

    device = {"platform": platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": len(records), "failed": raised}
    if args.trace:
        trace = None
        path = tracing.xplane_file(trace_dir)
        if path:
            trace = tracing.load_trace(path, chips=len(used))
        shutil.rmtree(trace_dir, ignore_errors=True)
        host_jobs = [r for r in records if not r.traced] or records
        ctx = SimpleNamespace(
            jobs=host_jobs, trace=trace, peaks=peaks,
            shapes={"m": problem.data.shape[0], "n": problem.data.shape[1],
                    "insert_widths": runner.insert_widths(warm),
                    "r_min": int(problem.arities.min())})
        metrics = {}
        for m in cell.per_layer:
            v = _value(bench.reader(m.name)(ctx), m.unit)
            if v is not None:
                metrics[m.name] = v
        if trace is not None and trace.devices:
            busy = tracing.busy_seconds(trace)
            device["busy_s"] = float(np.mean(busy))
            device["window_s"] = tracing.window_seconds(trace)
            result["breakdown"] = {"device_ops": tracing.top_ops(trace),
                                   "idle_gaps": tracing.idle_gaps(trace)}
    else:
        metrics = {}
        e2e = {"setup_s": setup_s, "dag_s": window_s / len(records)}
        for m in cell.end_to_end:
            metrics[m.name] = _value(e2e[m.name], m.unit)
    result["metrics"] = metrics
    result["device"] = device
    result["jobs"] = [{"wall_s": r.wall_s, **{f"{k}_s": v for k, v in
                                               r.spans.items() if k != "job"},
                       "compile_s": r.compile_s, "gc_s": r.gc_s,
                       "cpu_s": r.cpu_s, **r.switches} for r in records]

    # ---- correctness ---------------------------------------------------
    done = [a for a in answers if a is not None]
    judged = {}
    k0 = time.perf_counter()
    if done:
        pick = done[int(np.random.default_rng(args.seed).integers(len(done)))]
        if pick.members is not None:
            pick.prev = runner.previous_round(pick)
        values = checks.numbers(problem, cell.config, cell.traffic, pick)
        values["repeat_diff"] = sum(not a.same(pick) for a in done)
        judged = checks.judge(values, cell.config["limits"])
    result["correct"] = bool(done) and raised == 0 and checks.passed(judged)
    result["check_s"] = time.perf_counter() - k0
    result["checks"] = judged
    for name, v in judged.items():
        ok = "ok" if v["value"] <= v["limit"] else "FAIL"
        print(f"check {name} {v['value']!r} limit {v['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
