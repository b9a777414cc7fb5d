"""tpulint: rule fixtures pin exact (rule, line) findings; the gate test
runs the analyzer over the whole package against the committed baseline —
the pytest wiring of the CI lint (scripts/lint.sh is the shell spelling).

The analyzer is pure AST: fixtures under ``tpulint_fixtures/`` are never
imported, and the CLI tests prove linting works with JAX imports blocked.
"""

import json
import os
import subprocess
import sys

import pytest

from geomesa_tpu.analysis import (
    LintConfig,
    apply_baseline,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "geomesa_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpulint_fixtures")
BASELINE = os.path.join(REPO, ".tpulint-baseline.json")
# fixtures live outside the package tree: open the path-scoped rules up
FIXTURE_CFG = LintConfig(j002_paths=("",), j004_paths=("",), c001_paths=("",))


def _lint(name):
    vs = lint_paths([os.path.join(FIXTURES, name)], FIXTURE_CFG)
    return [(v.rule, v.line) for v in vs if not v.suppressed]


class TestRuleFixtures:
    """Each rule flags its known-bad fixture at exact lines and stays
    silent on the known-good twin."""

    @pytest.mark.parametrize("name,expected", [
        ("j001_bad.py",
         [("J001", 12), ("J001", 19), ("J001", 26), ("J001", 34)]),
        ("j002_bad.py",
         [("J002", 10), ("J002", 16), ("J002", 24), ("J002", 32)]),
        ("j003_bad.py",
         [("J003", 7), ("J003", 11), ("J003", 19), ("J003", 26),
          ("J003", 32)]),
        ("j004_bad.py",
         [("J004", 9), ("J004", 13), ("J004", 16), ("J004", 21)]),
        ("c001_bad.py",
         [("C001", 17), ("C001", 24), ("C001", 40)]),
        ("w001_bad.py",
         [("W001", 6)]),
    ])
    def test_bad_fixture_flagged(self, name, expected):
        assert _lint(name) == expected

    @pytest.mark.parametrize("name", [
        "j001_good.py", "j002_good.py", "j003_good.py", "j004_good.py",
        "c001_good.py", "w001_good.py",
    ])
    def test_good_fixture_clean(self, name):
        assert _lint(name) == []


class TestImportCanonicalization:
    def test_from_jax_import_resolves_as_jax(self):
        """``from jax import shard_map`` and an aliased module import both
        canonicalize to the jax API the taint/jit machinery matches."""
        import ast as _ast

        from geomesa_tpu.analysis.astutils import ImportMap

        tree = _ast.parse(
            "from jax import shard_map\n"
            "import jax.numpy as jnp\n")
        im = ImportMap(tree)
        assert im.names["shard_map"] == "jax.shard_map"
        assert im.names["jnp"] == "jax.numpy"


class TestWaivers:
    def test_same_line_waiver(self):
        src = ("import jax\n"
               "g = jax.jit(lambda x: x, static_argnums=[0])"
               "  # tpulint: disable=J003\n")
        vs = lint_source(src, "w.py", FIXTURE_CFG)
        assert [v.rule for v in vs] == ["J003"]
        assert vs[0].waived

    def test_next_line_waiver(self):
        src = ("import jax\n"
               "# tpulint: disable-next-line=J003\n"
               "g = jax.jit(lambda x: x, static_argnums=[0])\n")
        vs = lint_source(src, "w.py", FIXTURE_CFG)
        assert vs and all(v.waived for v in vs)

    def test_waiver_is_rule_scoped(self):
        src = ("import jax\n"
               "g = jax.jit(lambda x: x, static_argnums=[0])"
               "  # tpulint: disable=C001\n")
        vs = lint_source(src, "w.py", FIXTURE_CFG)
        # the J003 is NOT waived by a C001-scoped comment — and since the
        # C001 waiver suppresses nothing, it is itself flagged stale
        assert {(v.rule, v.waived) for v in vs} == {
            ("J003", False), ("W001", False)}

    def test_docstring_waiver_syntax_is_inert(self):
        """Waiver syntax QUOTED in a string/docstring is neither a live
        waiver nor a stale one (core.py documents the syntax in its own
        module docstring)."""
        src = ('DOC = """use # tpulint: disable=J003 to waive"""\n'
               "import jax\n"
               "g = jax.jit(lambda x: x, static_argnums=[0])\n")
        vs = lint_source(src, "w.py", FIXTURE_CFG)
        assert [(v.rule, v.waived) for v in vs] == [("J003", False)]

    def test_syntax_error_reported_not_raised(self):
        vs = lint_source("def broken(:\n", "b.py", FIXTURE_CFG)
        assert [v.rule for v in vs] == ["E000"]


class TestBaseline:
    def test_roundtrip_suppresses_then_new_violation_fails(self, tmp_path):
        bad = os.path.join(FIXTURES, "j003_bad.py")
        vs = lint_paths([bad], FIXTURE_CFG)
        bl = tmp_path / "bl.json"
        write_baseline(str(bl), vs)
        again = lint_paths([bad], FIXTURE_CFG)
        apply_baseline(again, load_baseline(str(bl)))
        assert all(v.baselined for v in again)
        # a NEW violation (not in the baseline) must still fail
        extra = lint_source(
            "import jax\ng = jax.jit(lambda x: x, static_argnums=[0])\n",
            "new.py", FIXTURE_CFG)
        apply_baseline(extra, load_baseline(str(bl)))
        assert any(not v.suppressed for v in extra)

    def test_committed_baseline_version(self):
        with open(BASELINE, encoding="utf-8") as f:
            data = json.load(f)
        assert data["version"] == 1
        assert isinstance(data["entries"], list)


class TestPackageGate:
    """THE gate: the package (and harness scripts) lint clean against the
    committed baseline. A new violation fails tier-1 right here."""

    def test_package_clean_against_baseline(self):
        vs = lint_paths([PKG], LintConfig())
        apply_baseline(vs, load_baseline(BASELINE))
        new = [v for v in vs if not v.suppressed]
        assert new == [], "\n".join(
            f"{v.path}:{v.line}: {v.rule} {v.message}" for v in new)

    def test_scripts_and_bench_clean(self):
        paths = [os.path.join(REPO, "scripts"),
                 os.path.join(REPO, "bench.py"),
                 os.path.join(REPO, "__graft_entry__.py")]
        vs = lint_paths(paths, LintConfig())
        apply_baseline(vs, load_baseline(BASELINE))
        new = [v for v in vs if not v.suppressed]
        assert new == [], "\n".join(
            f"{v.path}:{v.line}: {v.rule} {v.message}" for v in new)


class TestCli:
    def _run(self, *args, env_extra=None):
        env = dict(os.environ, GEOMESA_TPU_NO_JAX="1")
        env.update(env_extra or {})
        return subprocess.run(
            [sys.executable, "-m", "geomesa_tpu.analysis", *args],
            capture_output=True, text=True, cwd=REPO, env=env,
        )

    def test_gate_exits_zero(self):
        out = self._run(PKG, "--baseline", BASELINE)
        assert out.returncode == 0, out.stdout + out.stderr

    def test_violations_exit_nonzero(self):
        out = self._run(os.path.join(FIXTURES, "j003_bad.py"))
        assert out.returncode == 1
        assert "J003" in out.stdout

    def test_sarif_report_shape(self):
        out = self._run(os.path.join(FIXTURES, "j003_bad.py"),
                        "--format", "sarif")
        doc = json.loads(out.stdout)
        assert doc["version"] == "2.1.0"
        assert doc["$schema"].endswith("sarif-2.1.0.json")
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "tpulint"
        results = run["results"]
        assert {r["ruleId"] for r in results} == {"J003"}
        assert run["properties"]["summary"]["new"] == len(results)
        for r in results:
            loc = r["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"]
            assert loc["region"]["startLine"] >= 1
            assert "suppressions" not in r  # new violations: unsuppressed
        # the driver's rule metadata indexes every registered rule
        ids = [x["id"] for x in run["tool"]["driver"]["rules"]]
        assert ids == sorted(ids)
        for r in results:
            assert ids[r["ruleIndex"]] == r["ruleId"]

    def test_sarif_marks_suppressions(self):
        """A waived violation surfaces as a SARIF in-source suppression,
        not as a dropped result."""
        from geomesa_tpu.analysis.report import render_json
        from geomesa_tpu.analysis import lint_source

        src = ("import jax\n"
               "g = jax.jit(lambda x: x, static_argnums=[0])"
               "  # tpulint: disable=J003\n")
        doc = json.loads(render_json(lint_source(src, "w.py", FIXTURE_CFG)))
        (res,) = doc["runs"][0]["results"]
        assert res["level"] == "note"
        assert res["suppressions"][0]["kind"] == "inSource"

    def test_sarif_golden_file(self):
        """Golden-file pin of the full SARIF document shape for a known
        fixture (regenerate with tests/tpulint_fixtures/make_sarif_golden.py
        when the rule registry or report layout changes ON PURPOSE)."""
        from geomesa_tpu.analysis.report import render_json
        from geomesa_tpu.analysis import lint_source

        rel = "tests/tpulint_fixtures/j003_bad.py"
        with open(os.path.join(REPO, rel), encoding="utf-8") as f:
            src = f.read()
        doc = json.loads(render_json(lint_source(src, rel, FIXTURE_CFG)))
        with open(os.path.join(FIXTURES, "sarif_golden.json"),
                  encoding="utf-8") as f:
            golden = json.load(f)
        assert doc == golden

    def test_list_rules(self):
        out = self._run("--list-rules")
        for rid in ("J001", "J002", "J003", "J004", "C001"):
            assert rid in out.stdout
        assert out.returncode == 0

    def test_rule_filter(self):
        out = self._run(os.path.join(FIXTURES, "j001_bad.py"),
                        "--rules", "C001")
        assert out.returncode == 0  # J001 findings masked out

    def test_lints_without_jax_importable(self, tmp_path):
        """The no-JAX contract: linting succeeds even when importing jax
        raises (a poisoned stub shadows the real package)."""
        (tmp_path / "jax").mkdir()
        (tmp_path / "jax" / "__init__.py").write_text(
            "raise ImportError('tpulint must not import jax')\n")
        env = {"PYTHONPATH": str(tmp_path)}
        out = self._run(PKG, "--baseline", BASELINE, env_extra=env)
        assert out.returncode == 0, out.stdout + out.stderr
