"""BENCHMARK.json against the benchmark's contract, and the loader finding a
configuration, a cell and a metric that were added as files only."""
import json
import re
import shutil
from types import SimpleNamespace

import pytest

from perfbench import spec

ROOT = spec.BENCH_DIR.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_command(bench):
    assert set(bench) == KEYS
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench"]
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    # the full check must fit with 24 cells
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, cells // 2)


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("perfbench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg)
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        assert {"score_gap", "delete_gap", "polish_gain"} <= set(
            cfg["limits"])


def test_cells(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(names)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (spec.BENCH_DIR / "traffic" / f"{w['traffic']}.json").exists()


def test_metrics(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
        layers.setdefault(m["layer"].split(":")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    cells = {w["name"] for w in bench["workloads"]}
    for w in cells:
        assert any(w in m.get("workloads", cells) for m in bench["per_layer"])


def _tiny_ctx():
    return SimpleNamespace(jobs=[], trace=None, peaks=None, shapes={})


def test_loader_finds_a_config_cell_and_metric_added_as_files(tmp_path):
    bench_dir = tmp_path / "perfbench"
    shutil.copytree(spec.BENCH_DIR / "traffic", bench_dir / "traffic")
    shutil.copytree(spec.BENCH_DIR / "metrics", bench_dir / "metrics")
    shutil.copytree(spec.BENCH_DIR / "configs", bench_dir / "configs")
    # the new files
    cfg = json.loads((spec.BENCH_DIR / "configs" / "link_like-m5000.json")
                     .read_text())
    cfg["m"] = 20000
    (bench_dir / "configs" / "link_like-m20000.json").write_text(
        json.dumps(cfg))
    (bench_dir / "traffic" / "ges-x.json").write_text(json.dumps(
        {"algorithm": "ges", "counts_impl": "fused"}))
    (bench_dir / "metrics" / "steps_per_s.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec_json["configs"].append({
        "name": "link_like-m20000", "source": "x", "why": "x", "reduced": [],
        "file": "perfbench/configs/link_like-m20000.json"})
    spec_json["workloads"].append({
        "name": "link-ges-m20000", "config": "link_like-m20000",
        "traffic": "ges-x", "chips": 1, "why": "x"})
    spec_json["per_layer"].append({
        "name": "steps_per_s", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "x", "moves": "dag_s",
        "workloads": ["link-ges-m20000"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec_json))

    b = spec.Benchmark(tmp_path, bench_dir)
    cell = b.cell("link-ges-m20000")
    assert cell.config["m"] == 20000 and cell.traffic["counts_impl"] == "fused"
    assert [m.name for m in cell.per_layer][-1] == "steps_per_s"
    assert b.reader("steps_per_s")(_tiny_ctx()) == 42.0
    # a metric bound to other cells stays out of this one
    assert "ring_round_s" not in {m.name for m in cell.per_layer}
    assert "steps_per_s" not in {m.name for m in b.cell("link-ges").per_layer}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        spec.Benchmark(ROOT).cell("no-such-cell")
