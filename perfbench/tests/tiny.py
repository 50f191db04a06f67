"""A tiny copy of the benchmark for the CPU tests: the real cells, traffic
mixes and metric readers on test-only configurations (n=16, m=400)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
TINY = {"pigs_like-m5000": "tiny_pigs.json", "link_like-m5000": "tiny_link.json"}


RING_CELL = {"name": "pigs-ring4", "config": "pigs_like-m5000",
             "traffic": "ring-cges-l4", "chips": 4, "why": "the ring path"}


def make_root(tmp: Path) -> Path:
    """A root whose BENCHMARK.json is the real one with each configuration
    swapped for its tiny stand-in, and with the ring cell whether or not
    the real one measures it."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        c["file"] = str(DATA / TINY[c["name"]])
    if all(w["name"] != RING_CELL["name"] for w in spec["workloads"]):
        spec["workloads"].append(RING_CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_python(code: str, devices: int = 1, timeout: int = 600):
    """Run ``code`` in a fresh CPU process with ``devices`` host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=timeout, cwd=str(ROOT))
