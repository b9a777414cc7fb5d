"""Each cell end to end at its rehearsal size on the CPU: the answers
equal the reference's, the result line has the contract's keys, and a
run without a chip, or without the program, prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(args, cwd=ROOT, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=600)


def _wanted(kind, cell):
    return {m["name"] for m in BENCH[kind] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_matches_reference(cell, trace):
    p = _run(["--workload", cell, "--seed", str(2**31 + 7), "--seconds", "1",
              "--trace", str(trace), "--rehearse"])
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    got = set(out["metrics"])
    if trace:
        # the CPU trace has no device plane: only span and counter metrics
        device_only = {m["name"] for m in BENCH["per_layer"]
                       if m["source"] == "device_trace"}
        assert got == _wanted("per_layer", cell) - device_only
    else:
        assert got == _wanted("end_to_end", cell)


def test_no_chip_no_result():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearse"], cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
