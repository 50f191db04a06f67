"""Tests for repro.analysis — the three-pass static-analysis gate.

Layout mirrors the passes:

* lint fixtures — one true-positive AND one known-clean (FP-free) snippet
  per rule, plus suppression-comment semantics and the live-repo zero pin;
* trace contracts — unit checks of the jaxpr walkers on hand-built
  programs, then ONE full ``run_contract_checks()`` (module-scoped; it
  compiles the real programs) asserting zero findings, one-psum count
  paths and the zero-re-trace steady-state pin;
* VMEM budgets — repo defaults fit, genuinely over-budget configurations
  are rejected with a per-term breakdown;
* the RING_ASYNC_DEBUG regression — env set AFTER import is honoured;
* CLI — exit 0 on clean input, nonzero on a seeded violation, JSON shape.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import lint_paths, lint_source
from repro.analysis.findings import Finding, Report
from repro.analysis.vmem import (DEFAULT_BUDGET, DEFAULT_CONFIGS,
                                 check_config, footprint, run_vmem_checks)

REPO_SRC = Path(__file__).resolve().parents[1] / "src"


def rules_of(findings):
    return sorted(f.rule for f in findings)


def lint_snippet(source, path="src/repro/core/fake.py", rules=None):
    from repro.analysis.lint import RULES
    return lint_source(textwrap.dedent(source), path,
                       rules if rules is not None else RULES)


# ---------------------------------------------------------------------------
# Pass 1 — lint fixtures
# ---------------------------------------------------------------------------

class TestR001ImportTimeEnv:
    def test_module_level_get_flagged(self):
        fs = lint_snippet("""
            import os
            DEBUG = bool(int(os.environ.get("RING_ASYNC_DEBUG", "0")))
        """)
        assert rules_of(fs) == ["R001"]
        assert "RING_ASYNC_DEBUG" in fs[0].message

    def test_getenv_and_subscript_flagged(self):
        fs = lint_snippet("""
            import os
            A = os.getenv("REPRO_COUNTS_IMPL")
            B = os.environ["RING_PORT"]
        """)
        assert rules_of(fs) == ["R001", "R001"]

    def test_def_time_contexts_flagged(self):
        # decorator args and parameter defaults evaluate at import time
        fs = lint_snippet("""
            import os
            def f(impl=os.environ.get("REPRO_COUNTS_IMPL", "segment")):
                return impl
        """)
        assert rules_of(fs) == ["R001"]

    def test_function_body_read_clean(self):
        fs = lint_snippet("""
            import os
            def debug_enabled():
                return os.environ.get("RING_ASYNC_DEBUG", "0") == "1"
        """)
        assert fs == []

    def test_default_factory_lambda_clean(self):
        fs = lint_snippet("""
            import os
            import dataclasses
            @dataclasses.dataclass
            class Cfg:
                impl: str = dataclasses.field(
                    default_factory=lambda: os.environ.get(
                        "REPRO_COUNTS_IMPL", "segment"))
        """)
        assert fs == []

    def test_non_repo_names_and_writes_clean(self):
        # XLA_FLAGS mutation and non-REPRO_/RING_ reads are launch/ idiom
        fs = lint_snippet("""
            import os
            FLAGS = os.environ.get("XLA_FLAGS", "")
            os.environ["XLA_FLAGS"] = FLAGS + " --xla_foo"
        """)
        assert fs == []


class TestR002BareAssert:
    def test_assert_on_parameter_flagged(self):
        fs = lint_snippet("""
            def sweep(m, tile_m=256):
                assert m % tile_m == 0, (m, tile_m)
        """, path="src/repro/kernels/fake/fake.py")
        assert rules_of(fs) == ["R002"]
        assert "python -O" in fs[0].message

    def test_assert_on_shape_unpacked_names_flagged(self):
        # hq/hkv are not parameters but derive from q/k — taint propagates
        fs = lint_snippet("""
            def attn(q, k):
                b, hq, t, d = q.shape
                _, hkv, s, _ = k.shape
                assert hq % hkv == 0, (hq, hkv)
        """, path="src/repro/kernels/fake/fake.py")
        assert rules_of(fs) == ["R002"]

    def test_valueerror_pattern_clean(self):
        fs = lint_snippet("""
            def sweep(m, tile_m=256):
                if m % tile_m != 0:
                    raise ValueError(f"m={m} not a multiple of {tile_m}")
        """, path="src/repro/kernels/fake/fake.py")
        assert fs == []

    def test_assert_on_internal_constant_clean(self):
        fs = lint_snippet("""
            def f(x):
                table_size = 128
                assert table_size % 2 == 0
                return x
        """, path="src/repro/core/fake.py")
        assert fs == []

    def test_outside_target_packages_clean(self):
        src = """
            def sweep(m, tile_m=256):
                assert m % tile_m == 0
        """
        assert lint_snippet(src, path="src/repro/launch/driver.py") == []
        assert lint_snippet(src, path="tests/test_fake.py") == []


class TestR003ClassBodyEnvDefault:
    def test_dataclass_default_flagged(self):
        # the exact pre-PR 5 GESConfig bug shape
        fs = lint_snippet("""
            import os
            import dataclasses
            @dataclasses.dataclass
            class Cfg:
                impl: str = os.environ.get("REPRO_COUNTS_IMPL", "segment")
        """)
        assert rules_of(fs) == ["R003"]
        assert "default_factory" in fs[0].message

    def test_plain_class_attribute_flagged(self):
        fs = lint_snippet("""
            import os
            class Cfg:
                port = int(os.environ.get("RING_PORT", "9000"))
        """)
        assert rules_of(fs) == ["R003"]

    def test_default_factory_clean(self):
        fs = lint_snippet("""
            import os
            import dataclasses
            @dataclasses.dataclass
            class Cfg:
                impl: str = dataclasses.field(
                    default_factory=lambda: os.environ.get(
                        "REPRO_COUNTS_IMPL", "segment"))
        """)
        assert fs == []


class TestR004SilentDispatch:
    def test_chain_without_else_flagged(self):
        fs = lint_snippet("""
            def run(engine, x):
                if engine == "host":
                    return x
                elif engine == "fast":
                    return x * 2
        """)
        assert rules_of(fs) == ["R004"]
        assert "no else" in fs[0].message

    def test_chain_with_silent_else_flagged(self):
        fs = lint_snippet("""
            def run(counts_impl, x):
                if counts_impl == "segment":
                    return x
                elif counts_impl == "onehot":
                    return x * 2
                else:
                    return x * 3
        """)
        assert rules_of(fs) == ["R004"]
        assert "silent else" in fs[0].message

    def test_chain_with_raising_else_clean(self):
        fs = lint_snippet("""
            def run(engine, x):
                if engine == "host":
                    return x
                elif engine == "jax":
                    return x * 2
                else:
                    raise ValueError(f"unknown engine {engine!r}")
        """)
        assert fs == []

    def test_validated_scope_clean(self):
        # bdeu.py idiom: an up-front check_*/resolve_* call legalises chains
        fs = lint_snippet("""
            def run(impl, x):
                impl = resolve_impl(impl)
                if impl == "segment":
                    return x
                elif impl == "onehot":
                    return x * 2
        """)
        assert fs == []

    def test_single_branch_and_compound_conditions_clean(self):
        fs = lint_snippet("""
            def run(engine, x, fast):
                if engine == "host":
                    x = x + 1
                if engine == "jax" and fast:
                    return x
                elif engine == "host" and not fast:
                    return x * 2
                return x
        """)
        assert fs == []


class TestSuppression:
    def test_same_line_and_line_above(self):
        fs = lint_snippet("""
            import os
            A = os.environ.get("REPRO_X")  # repro: allow=R001
            # repro: allow=R001
            B = os.environ.get("REPRO_Y")
            C = os.environ.get("REPRO_Z")
        """)
        assert len(fs) == 1 and "REPRO_Z" in fs[0].message

    def test_allow_all_and_wrong_id(self):
        # NB a suppression also covers the line directly below it, so the
        # two fixtures are separated to keep allow=all from leaking onto B
        fs = lint_snippet("""
            import os
            A = os.environ.get("REPRO_X")  # repro: allow=all

            B = os.environ.get("REPRO_Y")  # repro: allow=R002
        """)
        assert len(fs) == 1 and "REPRO_Y" in fs[0].message

    def test_syntax_error_reported_not_raised(self):
        fs = lint_source("def broken(:\n", "src/repro/core/x.py")
        assert rules_of(fs) == ["R000"]


def test_live_repo_lint_clean():
    """The gate this PR establishes: zero findings across src/."""
    findings = lint_paths([str(REPO_SRC)])
    assert findings == [], "\n".join(f.format() for f in findings)


# ---------------------------------------------------------------------------
# Pass 2 — trace-contract walkers (unit) + the full suite (module-scoped)
# ---------------------------------------------------------------------------

class TestJaxprWalkers:
    def test_psum_counting_and_axis_check(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.analysis.contracts import (check_collective_axes,
                                              count_psums)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        mapped = jax.shard_map(
            lambda x: jax.lax.psum(x.sum(), "data"),
            mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False)
        jaxpr = jax.make_jaxpr(mapped)(jnp.ones((4,), jnp.float32))
        assert count_psums(jaxpr, "data") == 1
        assert count_psums(jaxpr, "ring") == 0
        assert check_collective_axes(jaxpr, {"data"}, "t") == []
        bad = check_collective_axes(jaxpr, {"ring"}, "t")
        assert rules_of(bad) == ["C001"]

    def test_while_carry_and_dtype_checks_clean_program(self):
        import jax
        import jax.numpy as jnp
        from repro.analysis.contracts import (check_dtypes,
                                              check_while_carries)

        def prog(x):
            return jax.lax.while_loop(
                lambda c: c[0] < 5,
                lambda c: (c[0] + 1, c[1] * jnp.float32(2.0)),
                (jnp.int32(0), x))

        jaxpr = jax.make_jaxpr(prog)(jnp.float32(1.0))
        assert check_while_carries(jaxpr, "t") == []
        assert check_dtypes(jaxpr, "t") == []

    def test_dtype_check_catches_float64(self):
        import jax
        import jax.numpy as jnp
        from repro.analysis.contracts import check_dtypes

        with jax.enable_x64(True):
            jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(
                jnp.asarray(1.0, jnp.float64))
        fs = check_dtypes(jaxpr, "t")
        assert fs and all(f.rule == "C003" for f in fs)


@pytest.fixture(scope="module")
def contract_report():
    """ONE full contracts run (compiles the real programs, ~1 min)."""
    from repro.analysis.contracts import run_contract_checks
    return run_contract_checks()


class TestLiveContracts:
    def test_zero_findings(self, contract_report):
        findings, _ = contract_report
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_every_count_path_has_exactly_one_psum(self, contract_report):
        _, info = contract_report
        paths = info["count_paths"]
        # all three single backends + both fused backends x insert/delete
        assert set(paths) == {
            "local_score[segment]", "local_score[onehot]",
            "local_score[pallas]",
            "insert_scores[fused]", "insert_scores[fused_pallas]",
            "delete_scores[fused]", "delete_scores[fused_pallas]",
        }
        assert all(v == 1 for v in paths.values()), paths

    def test_zero_steady_state_retraces(self, contract_report):
        """Regression pin: 3 same-shape rounds of the jitted ring /
        ges_jit / sweep programs must not grow a compilation cache."""
        _, info = contract_report
        assert info["retrace"] == {"ring": 0, "ges_jit": 0, "sweep": 0}

    def test_real_programs_were_traced(self, contract_report):
        _, info = contract_report
        programs = set(info["programs"])
        assert {"ges_jit_body", "ges_jit_body[restricted]",
                "ges_jit_body[cached]", "fuse_trace",
                "score_cache.lookup_or_compute"} <= programs
        assert any(p.startswith("ring[") for p in programs)
        assert any(p.startswith("sweep[") for p in programs)


# ---------------------------------------------------------------------------
# Pass 3 — VMEM budgets
# ---------------------------------------------------------------------------

class TestVmemBudgets:
    def test_repo_defaults_fit(self):
        findings, info = run_vmem_checks()
        assert findings == [], "\n".join(f.format() for f in findings)
        assert set(info["kernels"]) == set(DEFAULT_CONFIGS)

    def test_over_budget_flash_attention_rejected(self):
        # (2048, 2048) f32 logits + probs alone = 32 MiB > the 16 MiB core
        bad = check_config("flash_attention", block_q=2048, block_k=2048,
                           head_dim=128)
        assert bad is not None and bad.rule == "V001"
        assert "logits" in bad.message

    def test_over_budget_delete_sweep_rejected(self):
        # tile_m = 16384 makes the (q_pad, tile_m) bf16 one-hot slab
        # 128 MiB, over the kernel's 64 MiB scoped VMEM limit
        bad = check_config("bdeu_delete", max_q=4096, r_max=5,
                           tile_m=16384, n_slots=12)
        assert bad is not None and bad.rule == "V001"

    def test_budget_monotone_in_tiles(self):
        small = footprint("bdeu_sweep", max_q=4096, r_max=8,
                          tile_m=128).total_bytes
        big = footprint("bdeu_sweep", max_q=4096, r_max=8,
                        tile_m=512).total_bytes
        assert small < big

    def test_custom_budget_and_unknown_kernel(self):
        findings, _ = run_vmem_checks(budget=1024)   # 1 KiB: everything fails
        assert len(findings) == len(DEFAULT_CONFIGS)
        with pytest.raises(ValueError, match="unknown kernel"):
            footprint("nope")


# ---------------------------------------------------------------------------
# Satellite regression — RING_ASYNC_DEBUG read at call time
# ---------------------------------------------------------------------------

class TestRingAsyncDebugEnv:
    def test_env_set_after_import_is_honoured(self, monkeypatch, capsys):
        from repro.core import ring_async   # imported with the var unset
        monkeypatch.delenv("RING_ASYNC_DEBUG", raising=False)
        assert ring_async._debug_enabled() is False
        ring_async._dbg("quiet")
        assert capsys.readouterr().out == ""
        # setting AFTER import must flip it on — the pre-PR import-time
        # binding froze False here forever
        monkeypatch.setenv("RING_ASYNC_DEBUG", "1")
        assert ring_async._debug_enabled() is True
        ring_async._dbg("loud")
        assert "loud" in capsys.readouterr().out
        monkeypatch.setenv("RING_ASYNC_DEBUG", "0")
        assert ring_async._debug_enabled() is False


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCLI:
    def _run(self, *argv):
        from repro.analysis.__main__ import main
        return main(list(argv))

    def test_clean_repo_exits_zero(self, capsys):
        rc = self._run("--skip-contracts", str(REPO_SRC))
        out = capsys.readouterr().out
        assert rc == 0
        assert "0 finding(s)" in out

    def test_seeded_violation_exits_nonzero(self, tmp_path, capsys):
        bad = tmp_path / "core" / "bad.py"
        bad.parent.mkdir()
        bad.write_text(textwrap.dedent("""
            import os
            MODE = os.environ.get("REPRO_MODE", "fast")
            def f(m, tile=8):
                assert m % tile == 0
        """))
        rc = self._run("--skip-contracts", "--skip-vmem", str(tmp_path))
        out = capsys.readouterr().out
        assert rc == 1
        assert "R001" in out and "R002" in out

    def test_json_report_shape(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import os\nX = os.getenv('RING_X')\n")
        rc = self._run("--skip-contracts", "--skip-vmem", "--json",
                       str(bad))
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert report["ok"] is False
        assert [f["rule"] for f in report["findings"]] == ["R001"]
        assert report["passes_run"] == ["lint"]

    def test_rule_subset_flag(self, tmp_path, capsys):
        bad = tmp_path / "bad.py"
        bad.write_text("import os\nX = os.getenv('RING_X')\n")
        rc = self._run("--skip-contracts", "--skip-vmem",
                       "--rules", "R004", str(bad))
        capsys.readouterr()
        assert rc == 0          # R001 finding masked by the subset

    def test_vmem_budget_flag(self, capsys):
        rc = self._run("--skip-contracts", "--skip-lint",
                       "--vmem-budget", "1024")
        out = capsys.readouterr().out
        assert rc == 1 and "V001" in out

    @pytest.mark.slow
    def test_module_entrypoint_subprocess(self):
        """`python -m repro.analysis` end to end (lint+vmem; contracts are
        exercised in-process by the module fixture above)."""
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--skip-contracts",
             "--json", str(REPO_SRC)],
            capture_output=True, text=True,
            env={"PYTHONPATH": str(REPO_SRC), "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout)
        assert report["ok"] is True
        assert set(report["passes_run"]) == {"lint", "vmem"}


# ---------------------------------------------------------------------------
# Findings / Report plumbing
# ---------------------------------------------------------------------------

def test_report_roundtrip():
    r = Report()
    assert r.ok
    r.extend([Finding("R001", "x.py", 3, "msg", "X = 1")])
    r.passes_run.append("lint")
    assert not r.ok
    data = json.loads(r.to_json())
    assert data["findings"][0]["line"] == 3
    assert "R001" in Finding("R001", "x.py", 3, "msg").format()
