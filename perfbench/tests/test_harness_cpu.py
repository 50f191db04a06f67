"""Whole runs of the harness at a tiny size on the CPU, through a test-only
path that skips the look for a chip: sound runs come out correct, and runs
with the timed path broken underneath (``faults.py``) or with the bfloat16
control in the program's place come out not correct.  The real command
refuses to run without a TPU, and in a directory without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench.tests import tiny


def _drive(tmp_path, cell, devices=1, trace=0):
    root = tiny.make_root(tmp_path)
    r = tiny.run_python(
        "from perfbench.tests import faults; "
        f"faults.main({cell!r}, {str(root)!r}, trace={trace})",
        devices=devices)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _verdicts(report):
    out = {}
    for name, run in report.items():
        assert run["rc"] == 0 and run["result"], run["stderr_tail"]
        out[name] = run["result"]["correct"]
    return out


def _expect(report, faults):
    got = _verdicts(report)
    assert got == {"sound": True, **{f: False for f in faults}}, {
        k: v["result"]["checks"] for k, v in report.items()}


def test_cges_cell_sound_and_faults(tmp_path):
    from perfbench.tests.faults import FAULTS

    report = _drive(tmp_path, "pigs-cges-l4")
    _expect(report, FAULTS["cges"])
    sound = report["sound"]["result"]
    assert set(sound["metrics"]) == {"setup_s", "dag_s"}
    assert set(sound["checks"]) >= {"score_gap", "delete_gap", "polish_gain",
                                    "mask_diff", "fusion_diff", "member_gap",
                                    "finetune_gap"}
    assert {"compile_s", "gc_s", "cpu_s"} <= set(sound["jobs"][0])
    assert list(sound)[-1] == "checks"
    assert sound["device"]["platform"] == "cpu"


def test_ges_cell_traced_sound_and_faults(tmp_path):
    from perfbench.tests.faults import FAULTS

    report = _drive(tmp_path, "link-ges", trace=1)
    _expect(report, FAULTS["ges"])
    # no TPU plane on the CPU: only the host-side metric has something to
    # read, and the device metrics are left out rather than read as 0
    assert set(report["sound"]["result"]["metrics"]) == {
        "compile_in_window_s"}


def test_ring_cell_sound_and_faults(tmp_path):
    from perfbench.tests.faults import FAULTS

    report = _drive(tmp_path, "pigs-ring4", devices=4)
    _expect(report, FAULTS["ring_cges"])
    assert report["sound"]["result"]["device"]["count"] == 4


def _no_result(r):
    lines = r.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_real_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "link-ges",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(tiny.ROOT), env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0 and _no_result(r)
    assert "no TPU" in r.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tiny.ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, '.'); "
            "from perfbench.harness import main; "
            "sys.exit(main(['--workload', 'link-ges', '--seed', '1', "
            "'--seconds', '1'], require_tpu=False))")
    r = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and _no_result(r)
    assert "repro" in r.stderr


@pytest.mark.parametrize("seed", ["-1", "x"])
def test_bad_seed_is_refused(seed):
    from perfbench.harness import parse

    with pytest.raises(SystemExit):
        parse(["--workload", "link-ges", "--seed", seed, "--seconds", "1"])
