"""The yardstick's arithmetic: peaks by device kind, and the least work of
an insert sweep, which neither padding nor dtype may change."""
from types import SimpleNamespace

import pytest

from perfbench import roofline, spec, tracing
from perfbench.tracing import Device, Op, Trace


def test_peaks_are_keyed_by_device_kind_with_a_source():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_byte_per_s"] == 819e9 and p["bf16_flop_per_s"] == 197e12
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_insert_sweep_work_counts_codes_table_and_increments():
    nbytes, ops = roofline.insert_sweep_work(5000, 436, 3, 3)
    assert nbytes == 5000 * 436 + 2 * 5000 + 436 * 3 * 3 * 2
    assert ops == 5000 * 436
    assert roofline.count_bytes(255) == 1 and roofline.count_bytes(5000) == 2


def test_least_time_is_bytes_bound_for_the_sweep():
    p = roofline.peaks("TPU v5 lite")
    nbytes, ops = roofline.insert_sweep_work(5000, 724, 2, 2)
    t, bound = roofline.least_seconds(nbytes, ops, p)
    assert bound == "hbm" and t == pytest.approx(nbytes / 819e9)


def _share(hlo_text, seconds_per_call, shapes):
    ns = seconds_per_call * 1e9
    ops = [Op(tracing.op_base(hlo_text.format(i)), i * 2 * ns,
              (i * 2 + 1) * ns) for i in range(4)]
    trace = Trace([Device("/device:TPU:0", ops)], [("job", 0, 8 * ns)],
                  (0, 8 * ns))
    ctx = SimpleNamespace(trace=trace, peaks=roofline.peaks("TPU v5 lite"),
                          jobs=[], shapes=shapes)
    read = spec.Benchmark(spec.BENCH_DIR.parent).reader(
        "bdeu_sweep_insert_roofline")
    return read(ctx)["value"]


@pytest.mark.parametrize("padded_shape", [
    "s32[5000,724]", "s32[5120,768]", "s8[5120,768]", "bf16[5120,1024]"])
def test_roofline_share_ignores_padding_and_dtype(padded_shape):
    shapes = {"m": 5000, "n": 724, "insert_widths": [724], "r_min": 2}
    nbytes, ops = roofline.insert_sweep_work(5000, 724, 2, 2)
    least, _ = roofline.least_seconds(nbytes, ops,
                                      roofline.peaks("TPU v5 lite"))
    # a kernel that takes exactly the least time reads 100%, whatever the
    # shapes and dtypes the trace's op text shows
    text = ("%bdeu_sweep_insert.{} = f32[2,2,1024,768] custom-call("
            + padded_shape + ")")
    assert _share(text, least, shapes) == pytest.approx(100.0)
    assert _share(text, 10 * least, shapes) == pytest.approx(10.0)
