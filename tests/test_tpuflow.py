"""tpuflow: fixture tests pin exact (rule, line) findings per F-rule
family, the cross-module fixtures prove findings ride the whole-program
call graph (a purge reachable only through ``HubRegistry.close_all``
must count), the seeded ISSUE-7 / ISSUE-13 regression fixtures pin the
pre-fix shapes, the package gate runs the contract analysis over the
live tree, and the CLI / inventory / waiver-parity / incremental-cache
/ exit-code surfaces are covered end-to-end.

Pure AST like the other prongs: fixtures under ``tpuflow_fixtures/``
are never imported, and everything runs with JAX gated off."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from geomesa_tpu.analysis import LintConfig
from geomesa_tpu.analysis.core import AnalysisCrash, lint_paths
from geomesa_tpu.analysis.flow import (
    FLOW_RULE_IDS,
    analyze_flow_paths,
    contract_inventory,
)
from geomesa_tpu.analysis.race import analyze_race_paths
from geomesa_tpu.analysis.race.lockset import load_modules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "geomesa_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpuflow_fixtures")


def _flow(name, config=None):
    vs = analyze_flow_paths([os.path.join(FIXTURES, name)],
                            config or LintConfig())
    return [(os.path.basename(v.path), v.line, v.rule)
            for v in vs if not v.suppressed]


def _run_cli(*argv, env_extra=None, cwd=None):
    env = dict(os.environ, GEOMESA_TPU_NO_JAX="1")
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "geomesa_tpu.analysis", *argv],
        capture_output=True, text=True, env=env, cwd=cwd or REPO)


class TestRuleFixtures:
    """Each F-rule family flags its known-bad fixture at exact lines and
    stays silent on the known-good twin."""

    @pytest.mark.parametrize("name,expected", [
        # death (no delete_schema/rename), epoch non-monotonic + orphan,
        # unreachable purge, unknown surface name
        ("f001_bad.py", [
            ("f001_bad.py", 9, "F001"),
            ("f001_bad.py", 19, "F001"),
            ("f001_bad.py", 19, "F001"),
            ("f001_bad.py", 25, "F001"),
            ("f001_bad.py", 31, "F001"),
        ]),
        # unguarded sink via a helper, and a ROOT's own guard reference
        # must not bless the sink below it
        ("f002_bad.py", [
            ("f002_bad.py", 31, "F002"),
            ("f002_bad.py", 39, "F002"),
        ]),
        # f64 dtype in the certain band, certain calling the refine,
        # a cand superset decided on without refinement
        ("f003_bad.py", [
            ("f003_bad.py", 17, "F003"),
            ("f003_bad.py", 18, "F003"),
            ("f003_bad.py", 28, "F003"),
        ]),
        # stale tpuflow waivers, next-line and same-line forms
        ("w001_flow_bad.py", [
            ("w001_flow_bad.py", 10, "W001"),
            ("w001_flow_bad.py", 15, "W001"),
        ]),
    ])
    def test_bad_fixture_flagged(self, name, expected):
        assert _flow(name) == expected

    @pytest.mark.parametrize("name", [
        "f001_good.py", "f002_good.py", "f003_good.py",
        "w001_flow_good.py",
    ])
    def test_good_fixture_clean(self, name):
        assert _flow(name) == []

    def test_live_waiver_suppresses_f_rule(self):
        """The shared waiver tokenizer honors the tpuflow namespace: the
        good W001 fixture DOES contain a real F003, waived in source."""
        vs = analyze_flow_paths(
            [os.path.join(FIXTURES, "w001_flow_good.py")], LintConfig())
        waived = [v for v in vs if v.waived]
        assert [(v.rule, v.line) for v in waived] == [("F003", 13)]


class TestCrossModule:
    """The findings that REQUIRE the whole-program call graph."""

    def test_purge_through_hub_counts(self):
        """f001_x: ``drop_schema`` reaches the purge only through
        ``HubRegistry.close_all`` two modules away — reachable, so only
        the genuinely leaky mutation is flagged."""
        assert _flow("f001_x") == [("store.py", 14, "F001")]

    def test_shadow_taint_crosses_modules(self):
        """f002_x: root, pipeline helper, and sink live in three
        modules; the finding lands on the helper's sink call."""
        assert _flow("f002_x") == [("pipelinemod.py", 7, "F002")]


class TestSeedRegressions:
    """The ISSUE-7 and ISSUE-13 pre-fix shapes are the flow prong's seed
    corpus: each must be flagged at the exact (rule, line)."""

    def test_issue7_recreate_serves_dead_cache(self):
        vs = analyze_flow_paths(
            [os.path.join(FIXTURES, "issue7_recreate.py")], LintConfig())
        new = [v for v in vs if not v.suppressed]
        assert [(v.rule, v.line) for v in new] == [("F001", 13)]
        assert "death mutation" in new[0].message
        assert "deleted-then-recreated" in new[0].message

    def test_issue13_shadow_meter(self):
        vs = analyze_flow_paths(
            [os.path.join(FIXTURES, "issue13_shadow_meter.py")],
            LintConfig())
        new = [v for v in vs if not v.suppressed]
        assert [(v.rule, v.line) for v in new] == [("F002", 21)]
        assert "feedback sink CostTable.observe" in new[0].message


class TestPackageFlowGate:
    """The live tree holds its own contracts: zero unwaived F findings
    (fixes, not waivers — there are no F entries in the baseline), and
    the declared inventory covers the real cache/feedback planes."""

    def test_package_clean(self):
        vs = analyze_flow_paths([PKG], LintConfig())
        new = [v for v in vs if not v.suppressed]
        assert new == [], "\n".join(
            f"{v.path}:{v.line}: {v.rule} {v.message}" for v in new)

    def test_no_f_rule_waivers_in_tree(self):
        """The tentpole bar: live-tree F findings were FIXED, not waived
        — the tpuflow waiver namespace is unused inside the package."""
        out = subprocess.run(
            ["grep", "-rnE", r"# tpuflow: disable(-next-line)?=F[0-9]",
             PKG], capture_output=True, text=True)
        assert out.stdout == ""

    def test_contract_inventory_coverage(self):
        modules, errors = load_modules([PKG])
        assert errors == []
        inv = contract_inventory(modules, LintConfig())
        surfaces = {s["name"] for s in inv["cache_surfaces"]}
        assert len(surfaces) >= 10
        assert {"plan-cache", "agg-pyramids", "geoblocks-query-cache",
                "buffer-pool", "track-state-cache"} <= surfaces
        sinks = {d["fn"] for d in inv["feedback_sinks"]}
        assert len(sinks) >= 4
        assert {"PlanTable.record_query", "UsageMeter.observe",
                "SloEngine.observe"} <= sinks
        roots = {r["name"] for r in inv["shadow_planes"]}
        assert {"ContinuousAuditor", "InvariantSweeper"} <= roots
        roles = {(b["fn"], b["role"]) for b in inv["device_bands"]}
        assert ("trajectory.corridor:corridor_masks_f64",
                "refine") in roles
        assert ("parallel.query:cached_corridor_step", "cand") in roles

    def test_every_declared_purge_resolves(self):
        """Purge specs that fail to resolve are silent coverage holes —
        the inventory must show resolved keys for every non-immutable
        surface that declares a purge."""
        modules, _ = load_modules([PKG])
        inv = contract_inventory(modules, LintConfig())
        for s in inv["cache_surfaces"]:
            if s["purge"] and not s["immutable"]:
                assert s["purge"], s["name"]


class TestWaiverParity:
    """One tokenizer, three namespaces: each prong judges exactly its
    own waivers stale and leaves the other prongs' namespaces alone."""

    SRC = (
        "import threading\n"
        "x = 1  # tpulint: disable=C001\n"
        "y = 2  # tpurace: disable=R001\n"
        "z = 3  # tpuflow: disable=F001\n"
    )

    @pytest.fixture()
    def tree(self, tmp_path):
        p = tmp_path / "waivers.py"
        p.write_text(self.SRC)
        return str(p)

    def test_lint_judges_only_its_namespace(self, tree):
        vs = lint_paths([tree], LintConfig())
        w = [(v.rule, v.line) for v in vs if v.rule == "W001"]
        assert w == [("W001", 2)]

    def test_race_judges_only_its_namespace(self, tree):
        cfg = LintConfig(race_paths=("",), r003_paths=("",))
        vs = analyze_race_paths([tree], cfg)
        w = [(v.rule, v.line) for v in vs if v.rule == "W001"]
        assert w == [("W001", 3)]

    def test_flow_judges_only_its_namespace(self, tree):
        vs = analyze_flow_paths([tree], LintConfig())
        w = [(v.rule, v.line) for v in vs if v.rule == "W001"]
        assert w == [("W001", 4)]


class TestCli:
    """Exit codes, the contract inventory surface, and SARIF."""

    def test_flow_gate_exits_zero_on_package(self):
        out = _run_cli("--flow", PKG)
        assert out.returncode == 0, out.stdout + out.stderr

    def test_flow_bad_fixture_exits_one(self):
        out = _run_cli("--flow", os.path.join(FIXTURES, "f003_bad.py"))
        assert out.returncode == 1
        assert "F003" in out.stdout

    def test_contracts_inventory_json(self):
        out = _run_cli("--flow", "--contracts", PKG)
        assert out.returncode == 0, out.stderr
        inv = json.loads(out.stdout)
        assert len(inv["cache_surfaces"]) >= 10
        assert len(inv["feedback_sinks"]) >= 4

    def test_contracts_requires_flow(self):
        out = _run_cli("--contracts", PKG)
        assert out.returncode == 2
        assert "--contracts requires --flow" in out.stderr

    def test_flow_rules_filter_validation(self):
        out = _run_cli("--flow", "--rules", "J001", PKG)
        assert out.returncode == 2
        out = _run_cli("--rules", "F001", PKG)
        assert out.returncode == 2
        assert "--flow" in out.stderr

    def test_list_rules_includes_flow(self):
        out = _run_cli("--list-rules")
        assert out.returncode == 0
        for rid in FLOW_RULE_IDS:
            assert rid in out.stdout


class TestExitCodeAudit:
    """A crashed or partial analysis must never read as a clean run."""

    def test_contracts_parse_error_exits_one(self, tmp_path):
        """A syntax error silently shrinks the inventory: incomplete,
        not clean — the same audit that fixed ``--guards``."""
        (tmp_path / "ok.py").write_text("x = 1\n")
        (tmp_path / "broken.py").write_text("def f(:\n")
        out = _run_cli("--flow", "--contracts", str(tmp_path))
        assert out.returncode == 1
        assert "broken.py" in out.stderr

    def test_guards_parse_error_exits_one(self, tmp_path):
        (tmp_path / "broken.py").write_text("def f(:\n")
        out = _run_cli("--race", "--guards", str(tmp_path))
        assert out.returncode == 1
        assert "broken.py" in out.stderr

    def test_crashed_prong_exits_three_naming_file(self, monkeypatch,
                                                   capsys):
        """AnalysisCrash → exit 3 with the failing file in the message
        (red leg: the pre-audit behavior was a clean exit 0)."""
        from geomesa_tpu.analysis import __main__ as cli
        from geomesa_tpu.analysis import flow

        target = os.path.join(FIXTURES, "f001_good.py")

        def boom(paths, config=None):
            raise AnalysisCrash(target, "rule F001",
                                RuntimeError("synthetic"))

        monkeypatch.setattr(flow, "analyze_flow_paths", boom)
        rc = cli.main(["--flow", target])
        assert rc == 3
        err = capsys.readouterr().err
        assert "f001_good.py" in err and "rule F001" in err

    def test_lint_rule_crash_exits_three(self, monkeypatch, capsys):
        """The raise site itself: a rule crashing mid-check surfaces as
        AnalysisCrash naming the rule and the file being linted."""
        from geomesa_tpu.analysis import __main__ as cli
        from geomesa_tpu.analysis.rules import all_rules

        rule = all_rules()["J001"]

        def boom(mod, config):
            raise RuntimeError("synthetic rule crash")

        monkeypatch.setattr(type(rule), "check", staticmethod(boom))
        target = os.path.join(FIXTURES, "f001_good.py")
        rc = cli.main([target])
        assert rc == 3
        err = capsys.readouterr().err
        assert "rule J001" in err and "f001_good.py" in err

    def test_internal_error_exits_three(self, monkeypatch, capsys):
        from geomesa_tpu.analysis import __main__ as cli
        from geomesa_tpu.analysis import flow

        def boom(paths, config=None):
            raise RuntimeError("unexpected")

        monkeypatch.setattr(flow, "analyze_flow_paths", boom)
        rc = cli.main(["--flow", os.path.join(FIXTURES, "f001_good.py")])
        assert rc == 3
        assert "internal error" in capsys.readouterr().err


class TestIncremental:
    """--changed-only content-hash caches: warm runs skip re-analysis,
    edits invalidate, and --full is the escape hatch."""

    def _cli(self, tmp_path, *argv):
        return _run_cli(*argv, env_extra={
            "TPULINT_CACHE_DIR": str(tmp_path / "cache")})

    def test_edit_invalidates_cache(self, tmp_path):
        """Red/green: a warm cache must not mask a NEW violation
        introduced by an edit (the content hash, not mtime, is the
        key)."""
        tree = tmp_path / "tree"
        tree.mkdir()
        shutil.copy(os.path.join(FIXTURES, "f001_good.py"),
                    tree / "mod.py")
        out = self._cli(tmp_path, "--flow", "--changed-only", str(tree))
        assert out.returncode == 0, out.stdout + out.stderr
        # warm hit on the unchanged tree stays clean
        out = self._cli(tmp_path, "--flow", "--changed-only", str(tree))
        assert out.returncode == 0
        # the edit introduces a certain-band f64: must be flagged
        src = (tree / "mod.py").read_text()
        src += (
            "\n\nfrom geomesa_tpu.analysis.contracts import device_band\n"
            "import numpy as np\n\n\n"
            "@device_band(certain=True)\n"
            "def bad_step(xs):\n"
            "    return xs.astype(np.float64)\n"
        )
        (tree / "mod.py").write_text(src)
        out = self._cli(tmp_path, "--flow", "--changed-only", str(tree))
        assert out.returncode == 1
        assert "F003" in out.stdout

    def test_full_escape_hatch_reanalyzes(self, tmp_path):
        tree = tmp_path / "tree"
        tree.mkdir()
        shutil.copy(os.path.join(FIXTURES, "f003_bad.py"),
                    tree / "mod.py")
        out = self._cli(tmp_path, "--flow", "--changed-only", str(tree))
        assert out.returncode == 1
        out = self._cli(tmp_path, "--flow", "--changed-only", "--full",
                        str(tree))
        assert out.returncode == 1
        assert "F003" in out.stdout

    def test_warm_changed_only_halves_wall_time(self, tmp_path):
        """The lint.sh acceptance bound: the three-prong analysis with
        --changed-only on an UNCHANGED tree must cost ≤50% of the full
        run (in practice it is <5% — one hash pass, zero re-analysis)."""
        from geomesa_tpu.analysis import __main__ as cli

        targets = [PKG, os.path.join(REPO, "scripts"),
                   os.path.join(REPO, "bench.py")]
        os.environ["TPULINT_CACHE_DIR"] = str(tmp_path / "cache")
        try:
            t0 = time.monotonic()
            rc = cli.main(["--all-prongs", *targets, "--baseline",
                           os.path.join(REPO, ".tpulint-baseline.json"),
                           "--changed-only", "--full"])
            full_s = time.monotonic() - t0
            assert rc == 0
            t0 = time.monotonic()
            rc = cli.main(["--all-prongs", *targets, "--baseline",
                           os.path.join(REPO, ".tpulint-baseline.json"),
                           "--changed-only"])
            warm_s = time.monotonic() - t0
            assert rc == 0
        finally:
            os.environ.pop("TPULINT_CACHE_DIR", None)
        assert warm_s <= 0.5 * full_s, (
            f"warm --changed-only took {warm_s:.2f}s vs full "
            f"{full_s:.2f}s — the incremental cache is not being hit")


class TestSarifMultiProng:
    """--all-prongs --format sarif: ONE log, one run per prong, each
    with its own driver and rule metadata; F-rule suppressions survive
    the round trip."""

    def test_one_log_per_prong_drivers(self):
        out = _run_cli("--all-prongs", "--format", "sarif",
                       os.path.join(FIXTURES, "f001_good.py"))
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        names = [r["tool"]["driver"]["name"] for r in doc["runs"]]
        assert names == ["tpulint", "tpurace", "tpuflow", "tpusync"]
        flow_rules = {r["id"] for r in
                      doc["runs"][2]["tool"]["driver"]["rules"]}
        assert {"F001", "F002", "F003"} <= flow_rules
        lint_rules = {r["id"] for r in
                      doc["runs"][0]["tool"]["driver"]["rules"]}
        assert not lint_rules & {"F001", "R001"}

    def test_f_rule_suppression_round_trip(self):
        out = _run_cli("--all-prongs", "--format", "sarif",
                       os.path.join(FIXTURES, "w001_flow_good.py"))
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        flow_run = doc["runs"][2]
        results = flow_run["results"]
        f003 = [r for r in results if r["ruleId"] == "F003"]
        assert len(f003) == 1
        assert f003[0]["suppressions"][0]["kind"] == "inSource"
