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


FOUR_DEVICES = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
                "PYTHONPATH": str(ROOT)}


def _run(args, cwd=ROOT, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "benchmark/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=600)


def _info(stderr: str) -> dict:
    """The run's JSON info line on stderr (the line before the checks)."""
    return json.loads([ln for ln in stderr.splitlines() if ln.startswith("{")][-1])


def _copy_harness(dest, workloads):
    """The harness beside a ``BENCHMARK.json`` whose cells are ``workloads``."""
    shutil.copytree(ROOT / "benchmark", dest / "benchmark",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    (dest / "BENCHMARK.json").write_text(json.dumps({**BENCH, "workloads": workloads}))


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


@pytest.mark.parametrize("traffic", ["select_city", "count_city64"])
def test_four_chip_cell_builds_on_four_devices(tmp_path, traffic):
    cell = {"name": f"gdelt4.{traffic}", "config": "gdelt", "traffic": traffic,
            "chips": 4, "why": "a gdelt mix over a catalog sharded on four chips"}
    _copy_harness(tmp_path, [cell])
    p = _run(["--workload", cell["name"], "--seed", str(2**31 + 11), "--seconds", "1",
              "--trace", "0", "--rehearse"], cwd=tmp_path, env_extra=FOUR_DEVICES)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 4
    info = _info(p.stderr)
    assert info["z3_devices"] == [0, 1, 2, 3]
    assert len(info["memory_peak_bytes_by_device"]) == 4


def test_one_chip_cell_builds_on_one_of_four_devices():
    p = _run(["--workload", CELLS[0], "--seed", str(2**31 + 13), "--seconds", "1",
              "--trace", "0", "--rehearse"], env_extra=FOUR_DEVICES)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 1
    info = _info(p.stderr)
    assert info["z3_devices"] == [0]
    assert len(info["memory_peak_bytes_by_device"]) == 1


def test_index_on_other_devices_no_result(tmp_path):
    # a store whose index does not span the cell's chips fails set-up
    cell = dict(BENCH["workloads"][0], chips=4)
    _copy_harness(tmp_path, [cell])
    store_py = tmp_path / "benchmark" / "store.py"
    src = store_py.read_text()
    assert "mesh=make_mesh(chips)" in src
    store_py.write_text(src.replace("mesh=make_mesh(chips)", "mesh=make_mesh(1)"))
    p = _run(["--workload", cell["name"], "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearse"], cwd=tmp_path, env_extra=FOUR_DEVICES)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "want 4" in p.stderr
