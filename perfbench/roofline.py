"""Peaks of the chip and the least work a kernel call requires.

The peaks are a table kept with the benchmark (``peaks.json``), keyed by
JAX's ``device_kind``, with its source; a device not in the table is an
error, never a default.

The work a call requires is the algorithm's own minimum, from its unpadded
shapes, whatever tiling, padding or dtype the kernel chooses, so no
implementation can read above 100% and a kernel that replaces it is judged
against the same work.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def count_bytes(m: int) -> int:
    """Bytes that hold a count of up to m instances."""
    return max(1, math.ceil(math.log2(m + 1) / 8))


def insert_sweep_work(m: int, width: int, r_child: int, r_cand: int):
    """(bytes, ops) that one FES insert sweep of a child requires: the m x
    ``width`` candidate codes and the m child and m parent-configuration
    codes read once at one byte per code, the count table of the
    ``width`` candidate families written once (at least one parent
    configuration each), and one increment per instance and candidate."""
    reads = m * width + 2 * m
    table = width * r_cand * r_child * count_bytes(m)
    return reads + table, m * width


def least_seconds(nbytes: float, ops: float, peak: dict):
    """(seconds, bound) of the roofline: the larger of bytes over HBM
    bandwidth and operations over the chip's highest operation rate."""
    t_mem = nbytes / peak["hbm_byte_per_s"]
    t_ops = ops / max(peak["bf16_flop_per_s"], peak["int8_op_per_s"])
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
