"""Pass 3 — symbolic VMEM budgets for the Pallas kernels.

A Pallas kernel may use the TPU compiler's scoped VMEM limit: 16 MiB by
default, raised by the BDeu kernels to 64 MiB (a v5e core has 128 MiB).
Every Pallas kernel in this repo keeps an accumulator (or running state)
resident in VMEM across a sequential grid, plus per-step input blocks and
in-kernel one-hot/softmax temporaries — and nothing checks that a (tile,
max_q, r_max) configuration actually fits until the TPU compiler rejects it
at paper scale.  This pass computes the footprint
symbolically from the same parameters the kernels take, so an over-budget
configuration fails at analysis time, with a per-term breakdown instead of
a compiler error.

Model (documented heuristic, deliberately conservative):

* input/output blocks whose BlockSpec index map depends on a grid axis are
  counted twice (Pallas pipelines them double-buffered); blocks with a
  constant index map (revisited accumulators) are counted once;
* ``scratch_shapes`` count once;
* named in-kernel temporaries (the one-hot slabs, the (BQ, BK) logits/probs
  pair, the scatter-by-matmul chunk) count once each — these are the terms
  that actually dominate (a (256, 4096) one-hot is 4 MiB).

The kernels and their repo-default paper-scale configurations are
tabulated in ``DEFAULT_CONFIGS`` (tile defaults from the ops wrappers,
max_q at the entry points' 1024 and r_max at munin's 5).  The footprint models
do not check (8, 128) block alignment; tests/test_tpu_compile.py compiles
the kernels for a described v5e chip for that.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

from ..kernels.common import VMEM_LIMIT_BYTES
from .findings import Finding

MIB = 2 ** 20
# The compiler's default scoped VMEM limit per core, by platform — the gate
# for kernels that do not raise it (see KERNEL_BUDGETS).
VMEM_BUDGETS: Dict[str, int] = {
    "tpu": 16 * MIB,
    "tpu_v4": 16 * MIB,
    "tpu_v5e": 16 * MIB,
    "tpu_v5p": 16 * MIB,
}
DEFAULT_BUDGET = VMEM_BUDGETS["tpu"]

F32 = 4
I32 = 4
BF16 = 2


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class Term:
    name: str
    shape: Tuple[int, ...]
    elem_bytes: int = F32
    buffers: int = 1         # 2 = double-buffered streaming block

    @property
    def nbytes(self) -> int:
        return math.prod(self.shape) * self.elem_bytes * self.buffers


@dataclasses.dataclass
class Footprint:
    kernel: str
    params: Dict[str, int]
    terms: List[Term]

    @property
    def total_bytes(self) -> int:
        return sum(t.nbytes for t in self.terms)

    def check(self, budget: int = DEFAULT_BUDGET) -> Optional[Finding]:
        if self.total_bytes <= budget:
            return None
        top = sorted(self.terms, key=lambda t: -t.nbytes)[:3]
        detail = ", ".join(
            f"{t.name}{list(t.shape)}x{t.buffers}={t.nbytes / MIB:.1f}MiB"
            for t in top)
        return Finding(
            "V001", self.kernel, 0,
            f"VMEM footprint {self.total_bytes / MIB:.1f} MiB exceeds the "
            f"{budget / MIB:.0f} MiB budget with {self.params} — dominant "
            f"terms: {detail}; shrink the tile/chunk parameters")

    def to_dict(self) -> dict:
        return {
            "kernel": self.kernel,
            "params": self.params,
            "total_bytes": self.total_bytes,
            "total_mib": round(self.total_bytes / MIB, 3),
            "terms": {t.name: t.nbytes for t in self.terms},
        }


# ---------------------------------------------------------------------------
# Per-kernel symbolic footprints (mirror the kernels' BlockSpecs/scratch)
# ---------------------------------------------------------------------------

def bdeu_count_footprint(*, max_q: int = 1024, r_max: int = 5,
                         tile_m: int = 512) -> Footprint:
    """kernels/bdeu_count: transposed one-hot contraction, (r_pad, q_pad)
    accumulator revisited across the sequential m grid."""
    q_pad, r_pad = _round_up(max_q, 128), _round_up(r_max, 8)
    return Footprint("bdeu_count", dict(max_q=max_q, r_max=r_max,
                                        tile_m=tile_m), [
        Term("in:cfg", (1, tile_m), I32, buffers=2),
        Term("in:child", (1, tile_m), I32, buffers=2),
        Term("out:counts", (r_pad, q_pad), F32),          # constant index map
        Term("tmp:oh_cfg", (q_pad, tile_m), BF16),
        Term("tmp:oh_child", (r_pad, tile_m), BF16),
    ])


def bdeu_sweep_footprint(*, max_q: int = 1024, r_max: int = 5,
                         tile_m: int = 512) -> Footprint:
    """kernels/bdeu_sweep.sweep_counts: joint insert sweep; the
    (r_max, q_pad, 128) output block rides the (b, c) grid axes
    (double-buffered), revisited across m innermost."""
    q_pad = _round_up(max_q, 8)
    return Footprint("bdeu_sweep", dict(max_q=max_q, r_max=r_max,
                                        tile_m=tile_m), [
        Term("in:cfg", (1, tile_m), I32, buffers=2),
        Term("in:child", (1, tile_m), I32, buffers=2),
        Term("in:data", (tile_m, 128), I32, buffers=2),
        Term("out:counts", (r_max, q_pad, 128), F32, buffers=2),
        Term("tmp:oh_cfg", (q_pad, tile_m), BF16),
        Term("tmp:data_eq_a", (tile_m, 128), BF16),
    ])


def bdeu_delete_footprint(*, max_q: int = 1024, r_max: int = 5,
                          tile_m: int = 512, n_slots: int = 10,
                          chunk_q: int = 256) -> Footprint:
    """kernels/bdeu_sweep.delete_marginals: VMEM-resident family table +
    in-VMEM scatter-by-matmul marginalization into the (S + 1) output
    tables; n_slots <= floor(log2(max_q))."""
    q_pad, r_pad = _round_up(max_q, 128), _round_up(r_max, 8)
    return Footprint("bdeu_delete", dict(max_q=max_q, r_max=r_max,
                                         tile_m=tile_m, n_slots=n_slots,
                                         chunk_q=chunk_q), [
        Term("in:cfg", (1, tile_m), I32, buffers=2),
        Term("in:child", (1, tile_m), I32, buffers=2),
        Term("in:slot_maps", (n_slots, q_pad), I32),
        Term("out:marginals", (n_slots + 1, r_pad, q_pad), F32),
        Term("scratch:family_table", (r_pad, q_pad), F32),
        Term("tmp:oh_cfg", (q_pad, tile_m), BF16),
        Term("tmp:oh_child", (r_pad, tile_m), BF16),
        Term("tmp:scatter_onehot", (q_pad, chunk_q), F32),
        Term("tmp:marginal_acc", (r_pad, q_pad), F32),
    ])


def flash_attention_footprint(*, block_q: int = 128, block_k: int = 128,
                              head_dim: int = 128) -> Footprint:
    """kernels/flash_attention: online-softmax attention; q/out blocks ride
    the query grid, k/v the (sequential) KV grid, stats persist in scratch."""
    return Footprint("flash_attention", dict(block_q=block_q,
                                             block_k=block_k,
                                             head_dim=head_dim), [
        Term("in:q", (block_q, head_dim), F32, buffers=2),
        Term("in:k", (block_k, head_dim), F32, buffers=2),
        Term("in:v", (block_k, head_dim), F32, buffers=2),
        Term("out:o", (block_q, head_dim), F32, buffers=2),
        Term("scratch:acc", (block_q, head_dim), F32),
        Term("scratch:m", (block_q, 128), F32),
        Term("scratch:l", (block_q, 128), F32),
        Term("tmp:logits", (block_q, block_k), F32),
        Term("tmp:probs", (block_q, block_k), F32),
    ])


def ssd_scan_footprint(*, chunk: int = 128, head_dim_p: int = 64,
                       state_n: int = 128) -> Footprint:
    """kernels/ssd_scan: Mamba2 chunked scan; (N, P) state in scratch,
    chunk-local quadratic decay mask as the dominant temporary."""
    return Footprint("ssd_scan", dict(chunk=chunk, head_dim_p=head_dim_p,
                                      state_n=state_n), [
        Term("in:x", (chunk, head_dim_p), F32, buffers=2),
        Term("in:a", (chunk,), F32, buffers=2),
        Term("in:b", (chunk, state_n), F32, buffers=2),
        Term("in:c", (chunk, state_n), F32, buffers=2),
        Term("out:y", (chunk, head_dim_p), F32, buffers=2),
        Term("scratch:state", (state_n, head_dim_p), F32),
        Term("tmp:decay_mask", (chunk, chunk), F32),
        Term("tmp:cb", (chunk, chunk), F32),
        Term("tmp:y_intra+inter", (2 * chunk, head_dim_p), F32),
        Term("tmp:w", (chunk, state_n), F32),
    ])


KERNEL_FOOTPRINTS: Dict[str, Callable[..., Footprint]] = {
    "bdeu_count": bdeu_count_footprint,
    "bdeu_sweep": bdeu_sweep_footprint,
    "bdeu_delete": bdeu_delete_footprint,
    "flash_attention": flash_attention_footprint,
    "ssd_scan": ssd_scan_footprint,
}

# Paper-scale representative configurations: max_q = 1024 (the entry
# points' table bound), r_max = 5 (munin's largest arity), tiles at the
# ops-wrapper defaults (kernels/common.py), n_slots = floor(log2(max_q)).
DEFAULT_CONFIGS: Dict[str, Dict[str, int]] = {
    "bdeu_count": dict(max_q=1024, r_max=5, tile_m=512),
    "bdeu_sweep": dict(max_q=1024, r_max=5, tile_m=512),
    "bdeu_delete": dict(max_q=1024, r_max=5, tile_m=512, n_slots=10),
    "flash_attention": dict(block_q=128, block_k=128, head_dim=128),
    "ssd_scan": dict(chunk=128, head_dim_p=64, state_n=128),
}

# The BDeu kernels raise the compiler's scoped VMEM limit to
# VMEM_LIMIT_BYTES; the others run under its 16 MiB default.
KERNEL_BUDGETS: Dict[str, int] = {
    "bdeu_count": VMEM_LIMIT_BYTES,
    "bdeu_sweep": VMEM_LIMIT_BYTES,
    "bdeu_delete": VMEM_LIMIT_BYTES,
}


def kernel_budget(kernel: str) -> int:
    return KERNEL_BUDGETS.get(kernel, DEFAULT_BUDGET)


def footprint(kernel: str, **params) -> Footprint:
    if kernel not in KERNEL_FOOTPRINTS:
        raise ValueError(f"unknown kernel {kernel!r}; valid: "
                         f"{sorted(KERNEL_FOOTPRINTS)}")
    return KERNEL_FOOTPRINTS[kernel](**params)


def check_config(kernel: str, budget: Optional[int] = None,
                 **params) -> Optional[Finding]:
    """Budget-gate one kernel configuration (against its own VMEM limit
    unless ``budget`` is given); None when it fits."""
    return footprint(kernel, **params).check(
        kernel_budget(kernel) if budget is None else budget)


def run_vmem_checks(budget: Optional[int] = None,
                    configs: Optional[Dict[str, Dict[str, int]]] = None):
    """Footprint every kernel at its (default or given) configuration,
    against its own VMEM limit unless ``budget`` is given.

    Returns ``(findings, info)`` — info carries the full per-term breakdown
    for the JSON report (the budget table in the package docstring is
    generated from exactly this)."""
    configs = DEFAULT_CONFIGS if configs is None else configs
    findings: List[Finding] = []
    info = {"budget_bytes": budget, "kernels": {}}
    for kernel, params in configs.items():
        fp = footprint(kernel, **params)
        info["kernels"][kernel] = fp.to_dict()
        bad = fp.check(kernel_budget(kernel) if budget is None else budget)
        if bad is not None:
            findings.append(bad)
    return findings, info
