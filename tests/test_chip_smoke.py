"""chip_smoke.py rehearsed on the CPU mesh: every phase of the on-chip
bring-up run, at a tiny size, held to the same referee checks. The chip
run itself (``python chip_smoke.py``) refuses a CPU-only JAX."""

import json

import pytest

import chip_smoke


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


@pytest.mark.parametrize("chips", [1, 4])
def test_phases_pass_on_cpu_mesh(monkeypatch, capsys, chips):
    # the mesh sample sort only runs unasked on a TPU; ask for it here
    monkeypatch.setenv("GEOMESA_DEVICE_SORT_MIN_ROWS", "1000")
    out = chip_smoke.run(rows=6000, n_queries=8, seed=5, chips=chips)
    assert not any(out["health"].values())
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    phases = {x["phase"]: x for x in lines}
    assert phases["write_compact"]["device_sort"] is True
    assert phases["residency"]["devices"]["z3"] == list(range(chips))
    want = {"query", "select_many", "count_many", "count_many_loose",
            "sql_group_by"}
    if chips == 1:
        want |= {"density", "density_many", "http", "stream"}
    assert want <= set(phases)
    assert all(phases[p]["device_dispatches"] > 0 for p in want)


def test_wrong_answer_fails_the_run(monkeypatch):
    from geomesa_tpu.store.datastore import DataStore

    real = DataStore.count_many

    def off_by_one(self, *a, **k):
        return [c + 1 for c in real(self, *a, **k)]

    monkeypatch.setattr(DataStore, "count_many", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match="count_many"):
        chip_smoke.run(rows=3000, n_queries=4, seed=5, chips=1)


def test_host_failover_fails_the_run(monkeypatch):
    """An answer served by the host after a device failure is correct but
    not a bring-up: the run must fail on the counter."""
    import jax

    from geomesa_tpu.store.backends import TpuBackend

    def lost(*a, **k):
        raise jax.errors.JaxRuntimeError("UNAVAILABLE: device lost")

    monkeypatch.setattr(TpuBackend, "_mesh_select_positions", lost)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="no device dispatch|degraded"):
        chip_smoke.run(rows=3000, n_queries=4, seed=5, chips=1)
