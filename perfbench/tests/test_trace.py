"""The reduction from a profiler trace to the per-layer metrics, on small
traces recorded on TPU v5e chips (one tiny GES job: stage 1 on the host,
then one ``ges_jit`` call; one tiny ring job on four chips: stage 1,
``ring_cges``, the fine-tune) and on hand-made traces whose answers are
known."""
import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import spec, tracing
from perfbench.tracing import Device, Op, Trace

FIXTURE = Path(__file__).resolve().parent / "data" / "tiny_ges.xplane.pb"
RING_FIXTURE = FIXTURE.with_name("tiny_ring.xplane.pb.gz")
V5E = {"bf16_flop_per_s": 197e12, "int8_op_per_s": 393e12,
       "hbm_byte_per_s": 819e9}


def read(metric, ctx):
    return spec.Benchmark(spec.BENCH_DIR.parent).reader(metric)(ctx)


@pytest.fixture(scope="module")
def recorded():
    return tracing.load_trace(str(FIXTURE))


def test_recorded_trace_has_one_chip_and_the_harness_spans(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    names = {n for n, _, _ in recorded.spans}
    assert names == {"job", "partition", "ges"}
    lo, hi = recorded.window
    job = recorded.span_intervals("job")[0]
    assert (lo, hi) == job


def test_recorded_busy_is_inside_the_window(recorded):
    busy = tracing.busy_seconds(recorded)[0]
    assert 0 < busy < tracing.window_seconds(recorded)


def test_recorded_kernels_and_gaps(recorded):
    dev = recorded.devices[0]
    assert tracing.op_count(dev, "bdeu_sweep_insert") > 0
    assert all(o.name not in tracing.CONTAINERS
               for o in dev.leaf_ops())
    gaps = dict(tracing.idle_gaps(recorded))
    # stage 1 runs on the host only: its whole span is idle on the chip
    part = recorded.span_intervals("partition")[0]
    assert gaps["partition"] >= 0.9 * (part[1] - part[0]) / 1e9
    assert len(tracing.top_ops(recorded)) == 10


def test_recorded_metrics_read(recorded):
    ctx = SimpleNamespace(trace=recorded, peaks=V5E, jobs=[], shapes={
        "m": 200, "n": 10, "insert_widths": [10], "r_min": 2})
    idle = read("device_idle", ctx)
    share = read("pallas_share", ctx)
    roof = read("bdeu_sweep_insert_roofline", ctx)
    assert 0 < idle < 100 and 0 < share < 100
    assert 0 < roof["value"] < 100 and roof["bound"] == "hbm"
    assert read("ring_collective_share", ctx) is None


def test_op_base_strips_the_hlo_text_and_number():
    text = ("%bdeu_sweep_insert.15 = f32[3,3,1024,128]{3,2,1,0} "
            "custom-call(s32[1,1024]{1,0} %p)")
    assert tracing.op_base(text) == "bdeu_sweep_insert"
    assert tracing.op_base("%while.74 = (s32[]) while(...)") == "while"
    assert tracing.op_base("all-reduce.3") == "all-reduce"


@pytest.mark.parametrize("text, want", [
    ("%pmax.3 = f32[]{:T(128)} all-reduce(f32[] %x), channel_id=1", True),
    ("%collective-permute-start.1 = (s8[1,441,441]) "
     "collective-permute-start(s8[1,441,441] %p)", True),
    ("%pmax.3", True),
    ("%fusion.2 = f32[4] fusion(f32[] %all-reduce.1), kind=kLoop", False),
    ("%bdeu_sweep_insert.15 = f32[3,3,1024,128] custom-call(s32[1,1024] %a)",
     False)])
def test_collectives_are_found_by_opcode(text, want):
    assert tracing.is_collective(text) is want


def synthetic():
    """Two chips, 10 ns window; a while op holding a kernel and a
    collective; a host span covering the first gap."""
    d0 = Device("/device:TPU:0", [
        Op("while", 2, 8), Op("bdeu_sweep_insert", 2, 5),
        Op("pmax", 5, 6, collective=True), Op("fusion", 6, 8)])
    d1 = Device("/device:TPU:1", [Op("bdeu_count", 0, 10)])
    spans = [("job", 0, 10), ("partition", 0, 2), ("ring", 2, 10)]
    return Trace([d0, d1], spans, (0, 10))


def test_synthetic_busy_idle_and_names():
    t = synthetic()
    assert tracing.busy_seconds(t) == [6e-9, 10e-9]
    assert tracing.idle_gaps(t) == [["partition", 1e-9], ["ring", 1e-9]]
    ops = dict(tracing.top_ops(t))
    assert "while" not in ops and ops["bdeu_count"] == pytest.approx(5e-9)


def test_synthetic_metrics():
    ctx = SimpleNamespace(trace=synthetic(), peaks=V5E, jobs=[], shapes={})
    assert read("device_idle", ctx) == pytest.approx(20.0)
    assert read("pallas_share", ctx) == pytest.approx(100 * 13 / 16)
    # chip 0: 1 ns of collective in 6 ns busy inside the ring span
    assert read("ring_collective_share", ctx) == pytest.approx(100 / 6)


def test_ring_round_s_leaves_out_compilation_in_the_ring_span():
    jobs = [SimpleNamespace(spans={"partition": 1.0, "ring": 5.0},
                            span_compile={"ring": 2.0}, rounds=3),
            SimpleNamespace(spans={"partition": 1.0, "ring": 3.0},
                            span_compile={}, rounds=3)]
    ctx = SimpleNamespace(trace=None, peaks=None, jobs=jobs, shapes={})
    assert read("ring_round_s", ctx) == pytest.approx(1.0)
    assert read("partition_s", ctx) == pytest.approx(1.0)


def test_spans_count_the_compile_seconds_inside_each_span():
    clock = SimpleNamespace(seconds=0.0)
    spans = tracing.Spans(clock)
    with spans("job"):
        with spans("ring"):
            clock.seconds += 2.0
        clock.seconds += 0.5
    assert spans.compile == {"ring": 2.0, "job": 2.5}
    assert spans.seconds["job"] >= spans.seconds["ring"] > 0


@pytest.fixture(scope="module")
def ring_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("ring") / "tiny_ring.xplane.pb"
    with gzip.open(RING_FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.load_trace(str(path), chips=4)


def test_recorded_ring_trace_finds_the_exchange_and_the_pmax(ring_trace):
    assert [d.name for d in ring_trace.devices] == [
        f"/device:TPU:{i}" for i in range(4)]
    assert {"job", "partition", "ring", "finetune"} == {
        n for n, _, _ in ring_trace.spans}
    for d in ring_trace.devices:
        names = {o.name for o in d.ops if o.collective}
        assert {"pmax", "collective-permute-start"} <= names
    ctx = SimpleNamespace(trace=ring_trace, peaks=V5E, jobs=[], shapes={})
    assert 0 < read("ring_collective_share", ctx) < 100
    assert 0 < read("device_idle", ctx) < 100
