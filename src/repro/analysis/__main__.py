"""CLI entry: ``python -m repro.analysis`` — see the package docstring.

Exit codes: 0 = clean, 1 = findings, 2 = a pass crashed (still a gate
failure, but distinguishable in CI logs).
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback
from pathlib import Path


def _repo_src_default() -> str:
    """Default lint scope: the src/ tree this installed package lives in."""
    here = Path(__file__).resolve()
    src = here.parents[2]            # .../src/repro/analysis -> .../src
    return str(src if src.name == "src" else here.parents[1])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="repo-specific lint + jaxpr trace contracts + Pallas "
                    "VMEM budget gate (nonzero exit on any finding)")
    p.add_argument("paths", nargs="*", default=None,
                   help="files/dirs to lint (default: the repo's src/ tree)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the structured JSON report")
    p.add_argument("--skip-lint", action="store_true")
    p.add_argument("--skip-contracts", action="store_true")
    p.add_argument("--skip-vmem", action="store_true")
    p.add_argument("--fast", action="store_true",
                   help="contracts: skip the (slower) steady-state "
                        "re-trace execution pin, keep the trace checks")
    p.add_argument("--vmem-budget", type=int, default=None,
                   help="VMEM budget in bytes for every kernel (default: "
                        "each kernel's own scoped VMEM limit)")
    p.add_argument("--rules", default=None,
                   help="comma-separated lint rule subset (e.g. R001,R004)")
    args = p.parse_args(argv)

    if not args.skip_contracts and "jax" not in sys.modules:
        # The ring-program contract wants k=2 ring slots (+ a data axis)
        # even on CPU — force host devices BEFORE jax initializes, exactly
        # like launch/dryrun.  Harmless when jax was already imported (the
        # checks degrade to k=1 on a single device).
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    from .findings import Report
    report = Report()

    if not args.skip_lint:
        from .lint import RULES, lint_paths
        rules = (tuple(r.strip().upper() for r in args.rules.split(","))
                 if args.rules else RULES)
        paths = args.paths or [_repo_src_default()]
        report.extend(lint_paths(paths, rules))
        report.passes_run.append("lint")
        report.info["lint"] = {"paths": [str(p) for p in paths],
                               "rules": list(rules)}

    if not args.skip_contracts:
        from .contracts import run_contract_checks
        try:
            findings, info = run_contract_checks(
                check_retrace=not args.fast)
        except Exception:
            print(traceback.format_exc(), file=sys.stderr)
            return 2
        report.extend(findings)
        report.passes_run.append("contracts")
        report.info["contracts"] = info

    if not args.skip_vmem:
        from .vmem import run_vmem_checks
        findings, info = run_vmem_checks(args.vmem_budget)
        report.extend(findings)
        report.passes_run.append("vmem")
        report.info["vmem"] = info

    if args.as_json:
        print(report.to_json())
    else:
        for f in report.findings:
            print(f.format())
        print(f"repro.analysis: {len(report.findings)} finding(s) across "
              f"{'+'.join(report.passes_run) or 'no passes'}")
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
