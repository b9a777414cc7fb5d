"""tpusync: fixture tests pin exact (rule, line) findings per S-rule
family, the cross-module fixture proves budget costs ride the
whole-program fixpoint, the package gate holds the live tree to its
declared dispatch budgets, and the reconcile surface is proven against
a REAL staged-select ledger export — static bound vs measured rate,
red and green.

Pure AST like the other prongs: fixtures under ``tpusync_fixtures/``
are never imported, and the static analysis runs with JAX gated off.
Only the live-export tests touch a device path."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from geomesa_tpu.analysis import LintConfig
from geomesa_tpu.analysis.core import AnalysisCrash, lint_paths
from geomesa_tpu.analysis.flow import analyze_flow_paths
from geomesa_tpu.analysis.race import analyze_race_paths
from geomesa_tpu.analysis.race.lockset import _Project, load_modules
from geomesa_tpu.analysis.sync import (
    LEDGER_EXPORT_KIND,
    SYNC_RULE_IDS,
    analyze_sync_paths,
    load_ledger_export,
)
from geomesa_tpu.analysis.sync.contracts_scan import scan_sync_contracts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "geomesa_tpu")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpusync_fixtures")


def _sync(name, config=None, reconcile=None):
    vs = analyze_sync_paths([os.path.join(FIXTURES, name)],
                            config or LintConfig(), reconcile=reconcile)
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
    """Each S-rule family flags its known-bad fixture at exact lines and
    stays silent on the known-good twin."""

    @pytest.mark.parametrize("name,expected", [
        # two-dispatch seq over budget 1, constant 4-loop over budget 2,
        # and a malformed (non-literal) budget declaration
        ("s001_bad.py", [
            ("s001_bad.py", 13, "S001"),
            ("s001_bad.py", 21, "S001"),
            ("s001_bad.py", 30, "S001"),
        ]),
        # block_until_ready + np.asarray in the region, materialize two
        # calls down the graph, implicit bool() in a certain-band branch
        ("s002_bad.py", [
            ("s002_bad.py", 21, "S002"),
            ("s002_bad.py", 22, "S002"),
            ("s002_bad.py", 31, "S002"),
            ("s002_bad.py", 38, "S002"),
        ]),
        # direct step-in-for, dispatch behind a helper call in a while,
        # and the comprehension form
        ("s003_bad.py", [
            ("s003_bad.py", 14, "S003"),
            ("s003_bad.py", 26, "S003"),
            ("s003_bad.py", 32, "S003"),
        ]),
        # raw jax.jit / jax.pmap calls outside the factory discipline
        ("s004_bad.py", [
            ("s004_bad.py", 9, "S004"),
            ("s004_bad.py", 13, "S004"),
        ]),
        # stale tpusync waivers, same-line and next-line forms
        ("w001_sync_bad.py", [
            ("w001_sync_bad.py", 10, "W001"),
            ("w001_sync_bad.py", 13, "W001"),
        ]),
    ])
    def test_bad_fixture_flagged(self, name, expected):
        assert _sync(name) == expected

    @pytest.mark.parametrize("name", [
        "s001_good.py", "s002_good.py", "s003_good.py", "s004_good.py",
        "w001_sync_good.py",
    ])
    def test_good_fixture_clean(self, name):
        assert _sync(name) == []

    def test_s001_message_carries_witness_chain(self):
        vs = analyze_sync_paths(
            [os.path.join(FIXTURES, "s001_bad.py")], LintConfig())
        two_pass = next(v for v in vs if v.line == 13)
        assert "worst case is 2 dispatch(es)" in two_pass.message
        assert "line 16" in two_pass.message  # first step() of the pair
        looped = next(v for v in vs if v.line == 21)
        assert "4 dispatch(es)" in looped.message
        assert "×4 (loop)" in looped.message

    def test_s002_message_names_root_and_retire_escape(self):
        vs = analyze_sync_paths(
            [os.path.join(FIXTURES, "s002_bad.py")], LintConfig())
        deep = next(v for v in vs if v.line == 31)
        assert "@host_sync_free" in deep.message
        assert "materialize" in deep.message
        assert "# tpusync: retire" in deep.message
        certain = next(v for v in vs if v.line == 38)
        assert "@device_band(certain=True)" in certain.message

    def test_live_waiver_suppresses_s_rule(self):
        """The shared waiver tokenizer honors the tpusync namespace: the
        good W001 fixture DOES contain a real S003, waived in source."""
        vs = analyze_sync_paths(
            [os.path.join(FIXTURES, "w001_sync_good.py")], LintConfig())
        waived = [v for v in vs if v.waived]
        assert [(v.rule, v.line) for v in waived] == [("S003", 16)]

    def test_retired_sync_is_not_a_finding(self):
        """s002_good retires BOTH its pipeline-end awaits (same-line and
        next-line): no S002, and no stale-waiver W001 either — retire is
        a sync-site blessing, not a waiver."""
        vs = analyze_sync_paths(
            [os.path.join(FIXTURES, "s002_good.py")], LintConfig())
        assert vs == []


class TestCrossModule:
    """The findings that REQUIRE the whole-program cost fixpoint."""

    def test_budget_violation_across_modules(self):
        """s001_x: the budget holder's own body has zero dispatch sites
        — both dispatches live in ``work.py`` one call away, so the
        finding exists only if costs propagate over the call graph."""
        assert _sync("s001_x") == [("api.py", 10, "S001")]

    def test_cross_module_witness_expands_the_callee(self):
        vs = analyze_sync_paths(
            [os.path.join(FIXTURES, "s001_x")], LintConfig())
        (v,) = [x for x in vs if not x.suppressed]
        assert "count_and_gather" in v.message
        assert "inside" in v.message  # the expanded callee chain


class TestPackageSyncGate:
    """The live tree holds its own budgets: zero unwaived S findings,
    and the fused-path surfaces the ISSUE names all declare budgets."""

    def test_package_clean(self):
        targets = [PKG, os.path.join(REPO, "scripts"),
                   os.path.join(REPO, "bench.py")]
        vs = analyze_sync_paths(targets, LintConfig())
        new = [v for v in vs if not v.suppressed]
        assert new == [], "\n".join(
            f"{v.path}:{v.line}: {v.rule} {v.message}" for v in new)

    def test_declared_budget_coverage(self):
        """select / select_many / aggregate_many / matrix-scan /
        corridor: every fused-path surface carries a budget, the
        corridor kernel is sync-free, and the DataStore facade is the
        choreography boundary."""
        modules, errors = load_modules([PKG])
        assert errors == []
        c = scan_sync_contracts(_Project(modules), modules)
        assert c.errors == []
        budgets = {b.label: b.n for b in c.budgets}
        assert budgets["TpuBackend.select"] == 2
        assert budgets["TpuBackend.select_many_positions"] == 2
        assert budgets["DataStore.select_many"] == 2
        assert budgets["DataStore.aggregate_many"] == 1
        assert budgets["SubscriptionMatrix.scan_chunk"] == 1
        assert budgets["trajectory.corridor:tube_select_many"] == 2
        assert budgets["trajectory.corridor:_corridor_kernel"] == 1
        sigs = {b.label: b.signatures for b in c.budgets}
        assert sigs["TpuBackend.select"] == ("*:rows",)
        assert sigs["DataStore.aggregate_many"] == ("*:stats",)
        assert "trajectory.corridor:_corridor_kernel" in {
            d.label for d in c.sync_free}
        assert "DataStore" in {d.label for d in c.choreo}

    def test_in_tree_sync_waivers_are_live(self):
        """Every `# tpusync: disable` in the tree suppresses a real
        finding (the chunked/streaming loops reviewed in this PR) — a
        stale one would surface as W001 in the gate above; pin the
        count so silent drift is visible."""
        out = subprocess.run(
            ["grep", "-rlE", r"# tpusync: disable(-next-line)?=S[0-9]",
             PKG, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True)
        files = set(out.stdout.split())
        assert {os.path.join(PKG, "process", "join.py"),
                os.path.join(PKG, "stream", "pipeline.py"),
                os.path.join(REPO, "bench.py")} == files


class TestWaiverParity:
    """One tokenizer, four namespaces: each prong judges exactly its
    own waivers stale and leaves the other prongs' namespaces alone."""

    SRC = (
        "import threading\n"
        "x = 1  # tpulint: disable=C001\n"
        "y = 2  # tpurace: disable=R001\n"
        "z = 3  # tpuflow: disable=F001\n"
        "w = 4  # tpusync: disable=S001\n"
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

    def test_sync_judges_only_its_namespace(self, tree):
        vs = analyze_sync_paths([tree], LintConfig())
        w = [(v.rule, v.line) for v in vs if v.rule == "W001"]
        assert w == [("W001", 5)]


class TestCli:
    """Exit codes, rule-filter validation, and the reconcile guards."""

    def test_sync_gate_exits_zero_on_package(self):
        out = _run_cli("--sync", PKG)
        assert out.returncode == 0, out.stdout + out.stderr

    def test_sync_bad_fixture_exits_one(self):
        out = _run_cli("--sync", os.path.join(FIXTURES, "s003_bad.py"))
        assert out.returncode == 1
        assert "S003" in out.stdout

    def test_sync_rules_filter_validation(self):
        out = _run_cli("--sync", "--rules", "J001", PKG)
        assert out.returncode == 2
        out = _run_cli("--rules", "S001", PKG)
        assert out.returncode == 2
        assert "--sync" in out.stderr
        out = _run_cli("--rules", "S001,F001", PKG)
        assert out.returncode == 2
        assert "--all-prongs" in out.stderr

    def test_sync_rule_subset_runs(self):
        out = _run_cli("--sync", "--rules", "S004",
                       os.path.join(FIXTURES, "s003_bad.py"))
        assert out.returncode == 0, out.stdout + out.stderr
        out = _run_cli("--sync", "--rules", "S004",
                       os.path.join(FIXTURES, "s004_bad.py"))
        assert out.returncode == 1
        assert "S003" not in out.stdout

    def test_list_rules_includes_sync(self):
        out = _run_cli("--list-rules")
        assert out.returncode == 0
        for rid in SYNC_RULE_IDS:
            assert rid in out.stdout

    def test_reconcile_requires_sync(self, tmp_path):
        p = tmp_path / "ledger.json"
        p.write_text(json.dumps({
            "kind": LEDGER_EXPORT_KIND, "schema_version": 1,
            "entries": []}))
        out = _run_cli("--reconcile", str(p), PKG)
        assert out.returncode == 2
        assert "--sync" in out.stderr

    def test_reconcile_missing_file_is_usage_error(self):
        out = _run_cli("--sync", "--reconcile", "/nonexistent/ledger.json",
                       PKG)
        assert out.returncode == 2

    def test_reconcile_wrong_kind_is_usage_error(self, tmp_path):
        p = tmp_path / "ledger.json"
        p.write_text(json.dumps({"kind": "something-else",
                                 "schema_version": 1, "entries": []}))
        out = _run_cli("--sync", "--reconcile", str(p),
                       os.path.join(FIXTURES, "s001_good.py"))
        assert out.returncode == 2
        assert "roundtrip-ledger" in out.stderr

    def test_reconcile_wrong_schema_version_is_usage_error(self, tmp_path):
        p = tmp_path / "ledger.json"
        p.write_text(json.dumps({"kind": LEDGER_EXPORT_KIND,
                                 "schema_version": 99, "entries": []}))
        out = _run_cli("--sync", "--reconcile", str(p),
                       os.path.join(FIXTURES, "s001_good.py"))
        assert out.returncode == 2
        assert "schema_version" in out.stderr


class TestExitCodeAudit:
    """A crashed or partial sync analysis must never read as clean."""

    def test_crashed_sync_prong_exits_three(self, monkeypatch, capsys):
        from geomesa_tpu.analysis import __main__ as cli
        from geomesa_tpu.analysis import sync

        target = os.path.join(FIXTURES, "s001_good.py")

        def boom(paths, config=None, reconcile=None):
            raise AnalysisCrash(target, "rule S001",
                                RuntimeError("synthetic"))

        monkeypatch.setattr(sync, "analyze_sync_paths", boom)
        rc = cli.main(["--sync", target])
        assert rc == 3
        err = capsys.readouterr().err
        assert "s001_good.py" in err and "rule S001" in err

    def test_internal_error_exits_three(self, monkeypatch, capsys):
        from geomesa_tpu.analysis import __main__ as cli
        from geomesa_tpu.analysis import sync

        def boom(paths, config=None, reconcile=None):
            raise RuntimeError("unexpected")

        monkeypatch.setattr(sync, "analyze_sync_paths", boom)
        rc = cli.main(["--sync", os.path.join(FIXTURES, "s001_good.py")])
        assert rc == 3
        assert "internal error" in capsys.readouterr().err


class TestIncremental:
    """--changed-only warm path for the sync prong, and the reconcile
    cache bypass (ledger contents are outside the tree fingerprint)."""

    def _cli(self, tmp_path, *argv):
        return _run_cli(*argv, env_extra={
            "TPULINT_CACHE_DIR": str(tmp_path / "cache")})

    def test_edit_invalidates_cache(self, tmp_path):
        tree = tmp_path / "tree"
        tree.mkdir()
        shutil.copy(os.path.join(FIXTURES, "s001_good.py"),
                    tree / "mod.py")
        out = self._cli(tmp_path, "--sync", "--changed-only", str(tree))
        assert out.returncode == 0, out.stdout + out.stderr
        out = self._cli(tmp_path, "--sync", "--changed-only", str(tree))
        assert out.returncode == 0
        src = (tree / "mod.py").read_text()
        src += (
            "\n\n@dispatch_budget(0)\n"
            "def late(mesh, xs):\n"
            "    return cached_probe_step(mesh)(xs)\n"
        )
        (tree / "mod.py").write_text(src)
        out = self._cli(tmp_path, "--sync", "--changed-only", str(tree))
        assert out.returncode == 1
        assert "S001" in out.stdout

    def test_reconcile_bypasses_warm_cache(self, tmp_path):
        """A warm clean cache must not mask a fresh ledger divergence:
        --reconcile always analyzes live."""
        tree = tmp_path / "tree"
        tree.mkdir()
        shutil.copy(os.path.join(FIXTURES, "s001_good.py"),
                    tree / "mod.py")
        out = self._cli(tmp_path, "--sync", "--changed-only", str(tree))
        assert out.returncode == 0, out.stdout + out.stderr
        budget_mod = tree / "sel.py"
        budget_mod.write_text(
            "from geomesa_tpu.analysis.contracts import dispatch_budget\n"
            "\n\n"
            "def cached_sel_step(mesh):\n"
            "    return lambda x: x\n"
            "\n\n"
            "@dispatch_budget(2, signatures=('z2:*',))\n"
            "def select(mesh, xs):\n"
            "    step = cached_sel_step(mesh)\n"
            "    return step(step(xs))\n")
        out = self._cli(tmp_path, "--sync", "--changed-only", str(tree))
        assert out.returncode == 0, out.stdout + out.stderr
        ledger = tmp_path / "ledger.json"
        ledger.write_text(json.dumps({
            "kind": LEDGER_EXPORT_KIND, "schema_version": 1,
            "entries": [{"type": "pts", "signature": "z2:iv16:rows",
                         "queries": 2, "dispatches": 8}]}))
        out = self._cli(tmp_path, "--sync", "--changed-only",
                        "--reconcile", str(ledger), str(tree))
        assert out.returncode == 1
        assert "ledger reconcile" in out.stdout


class TestReconcile:
    """Static bound vs measured dispatch rate, red and green."""

    BUDGET_SRC = (
        "from geomesa_tpu.analysis.contracts import dispatch_budget\n"
        "\n\n"
        "def cached_sel_step(mesh):\n"
        "    return lambda x: x\n"
        "\n\n"
        "@dispatch_budget(2, signatures=('z2:*',))\n"
        "def select(mesh, xs):\n"
        "    step = cached_sel_step(mesh)\n"
        "    return step(step(xs))\n"
    )

    @pytest.fixture()
    def tree(self, tmp_path):
        p = tmp_path / "sel.py"
        p.write_text(self.BUDGET_SRC)
        return str(p)

    def _reconcile(self, tree, entries):
        vs = analyze_sync_paths([tree], LintConfig(), reconcile=entries)
        return [(v.line, v.rule) for v in vs if not v.suppressed]

    def test_measured_within_bound_is_clean(self, tree):
        assert self._reconcile(tree, [
            {"type": "pts", "signature": "z2:iv16:rows",
             "queries": 3, "dispatches": 6},
        ]) == []

    def test_measured_above_bound_flags_declaration(self, tree):
        found = self._reconcile(tree, [
            {"type": "pts", "signature": "z2:iv16:rows",
             "queries": 2, "dispatches": 8},
        ])
        assert found == [(8, "S001")]  # the @dispatch_budget line

    def test_unclaimed_signature_is_ignored(self, tree):
        assert self._reconcile(tree, [
            {"type": "pts", "signature": "scan:rows",
             "queries": 2, "dispatches": 50},
        ]) == []

    def test_entries_must_be_objects(self, tmp_path):
        p = tmp_path / "ledger.json"
        p.write_text(json.dumps({"kind": LEDGER_EXPORT_KIND,
                                 "schema_version": 1, "entries": [1, 2]}))
        with pytest.raises(ValueError, match="entries"):
            load_ledger_export(str(p))


class TestLedgerExportSurfaces:
    """The measured side: the plan table's export(), the web route, and the
    CLI puller all speak the one schema the analyzer validates."""

    def _charged_table(self):
        from geomesa_tpu.obs.ledger import QueryLedger
        from geomesa_tpu.obs.plantable import PlanTable

        t = PlanTable()
        ql = QueryLedger()
        ql.note_dispatch(0.0, 0.001)
        ql.note_dispatch(0.002, 0.003)
        ql.note_sync(0.003, 0.004)
        t.record_query("pts", "z2:iv16:rows", 5.0, ledger=ql)
        return t

    def test_export_round_trips_through_loader(self, tmp_path):
        doc = self._charged_table().export()
        assert doc["kind"] == LEDGER_EXPORT_KIND
        assert doc["schema_version"] == 1
        p = tmp_path / "ledger.json"
        p.write_text(json.dumps(doc))
        (e,) = load_ledger_export(str(p))
        assert e["type"] == "pts"
        assert e["signature"] == "z2:iv16:rows"
        assert e["queries"] == 1
        assert e["dispatches"] == 2
        assert e["syncs"] == 1

    def test_web_route_serves_the_export_schema(self):
        import io

        from geomesa_tpu.obs import plantable
        from geomesa_tpu.store.datastore import DataStore
        from geomesa_tpu.web.app import GeoMesaApp

        app = GeoMesaApp(DataStore(backend="tpu"))
        prev = plantable.install(self._charged_table())
        try:
            def call(query):
                environ = {
                    "REQUEST_METHOD": "GET",
                    "PATH_INFO": "/api/obs/ledger",
                    "QUERY_STRING": query,
                    "CONTENT_LENGTH": "0",
                    "wsgi.input": io.BytesIO(b""),
                }
                out = {}

                def start_response(status, headers):
                    out["status"] = int(status.split()[0])

                body = b"".join(app(environ, start_response))
                return out["status"], json.loads(body)

            status, doc = call("format=json")
            assert status == 200
            assert doc["kind"] == LEDGER_EXPORT_KIND
            assert doc["schema_version"] == 1
            assert doc["entries"][0]["dispatches"] == 2
            status, doc = call("")  # format optional, json is the default
            assert status == 200
            status, doc = call("format=csv")
            assert status == 400
        finally:
            plantable.install(prev)

    def test_cli_export_writes_loader_valid_file(self, tmp_path):
        import argparse
        import threading
        from wsgiref.simple_server import WSGIRequestHandler, make_server

        from geomesa_tpu.cli.__main__ import cmd_obs_ledger_export
        from geomesa_tpu.obs import plantable
        from geomesa_tpu.store.datastore import DataStore
        from geomesa_tpu.web.app import GeoMesaApp

        class _Quiet(WSGIRequestHandler):
            def log_message(self, *a):
                pass

        app = GeoMesaApp(DataStore(backend="tpu"))
        httpd = make_server("127.0.0.1", 0, app, handler_class=_Quiet)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        prev = plantable.install(self._charged_table())
        out_path = tmp_path / "ledger.json"
        try:
            cmd_obs_ledger_export(argparse.Namespace(
                url=f"http://127.0.0.1:{httpd.server_address[1]}",
                timeout=10.0, output=str(out_path), limit=32, json=False))
        finally:
            plantable.install(prev)
            httpd.shutdown()
        (e,) = load_ledger_export(str(out_path))
        assert e["signature"] == "z2:iv16:rows"
        assert e["dispatches"] == 2


class TestReconcileLiveExport:
    """The acceptance pin: a --sync --reconcile pass over a ledger
    exported from a REAL staged-select run reports zero divergence for
    the staged signature — and a tampered export flags the declaration."""

    @pytest.fixture(scope="class")
    def export_entries(self, tmp_path_factory):
        import numpy as np

        from geomesa_tpu.geometry.types import Point
        from geomesa_tpu.obs import ledger as ledger_mod
        from geomesa_tpu.obs import plantable
        from geomesa_tpu.store import backends
        from geomesa_tpu.store.datastore import DataStore

        ds = DataStore(backend="tpu")
        ds.create_schema("pts", "name:String,dtg:Date,*geom:Point")
        rng = np.random.default_rng(5)
        t0 = 1_500_000_000_000
        ds.write("pts", [
            {"name": f"n{i % 3}", "dtg": t0 + i * 1000,
             "geom": Point(float(rng.uniform(-170, 170)),
                           float(rng.uniform(-60, 60)))}
            for i in range(300)
        ], fids=[f"f{i}" for i in range(300)])
        ds.compact("pts")
        # force the staged two-phase select (count -> host sizing ->
        # gather): the multi-dispatch signature the budgets must cover
        prev_slots = backends._ONE_PASS_MAX_SLOTS
        backends._ONE_PASS_MAX_SLOTS = 0
        cql = "BBOX(geom,-50,-40,50,40)"
        try:
            ds.query("pts", cql)  # compile the staged steps
            prev = plantable.install()
            try:
                for _ in range(3):
                    ds.query("pts", cql)
                doc = ledger_mod.table().export()
            finally:
                plantable.install(prev)
        finally:
            backends._ONE_PASS_MAX_SLOTS = prev_slots
        path = tmp_path_factory.mktemp("ledger") / "ledger.json"
        path.write_text(json.dumps(doc))
        return load_ledger_export(str(path))

    def _analyze(self, entries):
        targets = [os.path.join(PKG, "store", "backends.py"),
                   os.path.join(PKG, "store", "datastore.py")]
        vs = analyze_sync_paths(targets, LintConfig(rules=("S001",)),
                                reconcile=entries)
        return [v for v in vs if not v.suppressed]

    def test_staged_select_is_multi_dispatch(self, export_entries):
        rows = [e for e in export_entries
                if e["signature"].endswith(":rows") and e["queries"]]
        assert rows, export_entries
        assert any(e["dispatches"] / e["queries"] >= 2.0 for e in rows)

    def test_live_export_reconciles_clean(self, export_entries):
        assert self._analyze(export_entries) == [], (
            "staged select diverged from its declared budget")

    def test_tampered_export_flags_declaration(self, export_entries):
        tampered = [dict(e, dispatches=e["dispatches"] * 5)
                    for e in export_entries]
        found = self._analyze(tampered)
        assert found, "5x the measured rate must exceed the budget"
        assert all(v.rule == "S001" for v in found)
        assert any("ledger reconcile" in v.message for v in found)


class TestSarifMultiProng:
    """--all-prongs --format sarif: one run per prong including
    tpusync, S-rule suppressions survive, and the full multi-prong
    document shape is pinned as a golden file."""

    def test_four_driver_runs(self):
        out = _run_cli("--all-prongs", "--format", "sarif",
                       os.path.join(FIXTURES, "w001_sync_good.py"))
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        names = [r["tool"]["driver"]["name"] for r in doc["runs"]]
        assert names == ["tpulint", "tpurace", "tpuflow", "tpusync"]
        sync_rules = {r["id"] for r in
                      doc["runs"][3]["tool"]["driver"]["rules"]}
        assert sync_rules == {"S001", "S002", "S003", "S004", "W001"}
        lint_rules = {r["id"] for r in
                      doc["runs"][0]["tool"]["driver"]["rules"]}
        assert not lint_rules & sync_rules - {"W001"}

    def test_s_rule_suppression_round_trip(self):
        out = _run_cli("--all-prongs", "--format", "sarif",
                       os.path.join(FIXTURES, "w001_sync_good.py"))
        assert out.returncode == 0, out.stderr
        doc = json.loads(out.stdout)
        sync_run = doc["runs"][3]
        s003 = [r for r in sync_run["results"] if r["ruleId"] == "S003"]
        assert len(s003) == 1
        assert s003[0]["suppressions"][0]["kind"] == "inSource"

    def test_multi_prong_golden_file(self, monkeypatch):
        """Golden-file pin of the --all-prongs SARIF document shape
        (regenerate with tests/tpulint_fixtures/make_sarif_golden.py
        when the registry or layout changes ON PURPOSE)."""
        from geomesa_tpu.analysis import lint_source
        from geomesa_tpu.analysis.report import render_json_multi

        monkeypatch.chdir(REPO)  # the golden pins repo-relative URIs

        lint_fix = os.path.join(REPO, "tests", "tpulint_fixtures")
        rel = "tests/tpulint_fixtures/j003_bad.py"
        cfg = LintConfig(j002_paths=("",), j004_paths=("",),
                         c001_paths=("",))
        with open(os.path.join(lint_fix, "j003_bad.py"),
                  encoding="utf-8") as f:
            src = f.read()
        doc = json.loads(render_json_multi([
            ("tpulint", lint_source(src, rel, cfg)),
            ("tpurace", analyze_race_paths([rel], cfg)),
            ("tpuflow", analyze_flow_paths([rel], cfg)),
            ("tpusync", analyze_sync_paths([rel], cfg)),
        ]))
        with open(os.path.join(lint_fix, "sarif_multi_golden.json"),
                  encoding="utf-8") as f:
            golden = json.load(f)
        assert doc == golden
