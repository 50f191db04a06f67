"""repro.analysis — the repo's static-analysis layer: a three-pass checker
(`python -m repro.analysis`) that turns this codebase's recurring bug
classes into machine-enforced invariants.  Exit status is nonzero on any
finding, ``--json`` emits a structured report, and CI runs it as a
blocking gate.

Rule catalog — every id encodes a bug this repo actually shipped
=================================================================

**Pass 1 — AST lint** (:mod:`repro.analysis.lint`)

``R001`` *import-time env read of ``REPRO_*`` / ``RING_*`` config.*
    History: ``GESConfig.counts_impl`` was a plain dataclass default bound
    at class creation, so ``REPRO_COUNTS_IMPL`` set after ``import repro``
    was silently ignored (fixed in PR 5 with the ``default_factory``
    pattern); the same import-time binding then survived in
    ``core/ring_async.py``'s ``RING_ASYNC_DEBUG`` until this PR.  Config
    env vars must be read at call time.

``R002`` *bare ``assert`` validating caller-supplied values in ``core/``,
    ``kernels/`` or ``models/``.*  History: ``ring_cges``'s k-mismatch
    assert vanished under ``python -O`` and resurfaced as an opaque
    shard_map shape error (named ``ValueError`` since PR 7) — but every
    kernel package still guarded its tile-divisibility contracts with
    asserts until this PR.  Shape/argument contracts must raise
    ``ValueError`` so they survive optimized mode (CI runs a
    ``python -O`` smoke leg to prove it).

``R003`` *class-body defaults capturing env state at class creation.*
    The dataclass-shaped special case of R001 (the exact pre-PR 5
    ``GESConfig`` bug): a field default like ``x: str =
    os.environ.get(...)`` evaluates once when the class is created.  Use
    ``dataclasses.field(default_factory=lambda: ...)``.

``R004`` *silent engine-dispatch fallthrough.*  History: before PR 3 an
    unknown ``counts_impl`` silently dispatched to the segment engine, so
    a typo'd backend ran the wrong code with no error.  A chain of
    >= 2 ``X == "literal"`` branches on a dispatch variable
    (``counts_impl`` / ``engine`` / ``fusion_engine`` / ``impl`` /
    ``backend``) must either raise in its ``else`` or sit in a function
    that validates up front (``check_*`` / ``single_impl`` /
    ``resolve_*`` — how ``core/bdeu.py``'s chains stay legal).

Suppression: ``# repro: allow=R002`` (comma-separated ids, or
``allow=all``) on the flagged line or the line directly above.

**Pass 2 — trace contracts** (:mod:`repro.analysis.contracts`)

Walks the jaxprs of the REAL programs — ``sweep`` on all three backends,
``ges_jit_body`` (full-n / restricted / cached), the restricted (W, n)
ring program, ``fusion.fuse_trace``, ``score_cache.lookup_or_compute``:

``C001``  every collective (psum/ppermute/pmax/all_gather/axis_index)
          names a mesh-declared axis.
``C002``  ``lax.while_loop`` carries are fixed — shape, dtype and
          weak-type identical between loop input and body output.
``C003``  no float64/complex128 aval anywhere in the eqn graph.
``C004``  each ``data_axis_name`` count path rebuilds its global table
          with EXACTLY one psum (the additive-counts contract of PR 6).
``C005``  zero re-traces across steady-state same-shape rounds of the
          jitted sweep / ges_jit / ring programs (compilation-cache pin).

**Pass 3 — VMEM budgets** (:mod:`repro.analysis.vmem`)

Symbolic per-kernel VMEM footprints from the same tile/grid parameters the
kernels take, gated against the scoped VMEM limit each kernel runs under
(64 MiB for the BDeu kernels, the compiler's 16 MiB default otherwise) — so
a config that would only fail at TPU compile time at paper scale fails here
first.  Block alignment is not modelled; tests/test_tpu_compile.py compiles
the BDeu kernels for a described v5e chip.  Repo-default paper-scale table
(max_q=1024, r_max=5, tile_m=512; ``V001`` on overflow):

==================  ==========  ====================================
kernel              footprint   dominant term
==================  ==========  ====================================
bdeu_count           1.05 MiB   (q_pad, tile_m) bf16 one-hot slab
bdeu_sweep           6.63 MiB   (r_max, q_pad, 128) counts block x2
bdeu_delete          2.46 MiB   (q_pad, tile_m) bf16 one-hot slab
flash_attention      0.81 MiB   (BQ, BK) logits/probs pair
ssd_scan             0.66 MiB   (Q, Q) decay mask
==================  ==========  ====================================

CLI
===

``python -m repro.analysis [paths] [--json] [--skip-lint]
[--skip-contracts] [--skip-vmem] [--fast] [--vmem-budget BYTES]``

Default paths: ``src/`` (resolved relative to the repo root).  The
contracts pass forces extra host devices (like ``launch/dryrun``) so the
ring program traces at k = 2 with a data axis even on CPU CI.
"""
from .findings import Finding, Report
from .lint import RULES, lint_paths, lint_source
from .vmem import (DEFAULT_BUDGET, DEFAULT_CONFIGS, VMEM_BUDGETS,
                   check_config, footprint, run_vmem_checks)

__all__ = [
    "Finding", "Report", "RULES", "lint_paths", "lint_source",
    "DEFAULT_BUDGET", "DEFAULT_CONFIGS", "VMEM_BUDGETS", "check_config",
    "footprint", "run_vmem_checks",
]
