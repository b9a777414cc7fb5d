"""The whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` comes out false for each fault a cell can have."""

import json

import numpy as np
import pytest

from benchmark import run


def _patch(monkeypatch, name, wrap):
    from geomesa_tpu.store.datastore import DataStore

    orig = getattr(DataStore, name)
    monkeypatch.setattr(DataStore, name, lambda self, *a, **kw: wrap(orig(self, *a, **kw)))


def _drop_a_row(result):
    t = result.table
    if len(t):
        result.table = t.take(np.arange(1, len(t)))
    return result


def _alter_a_count(counts):
    return [counts[0] + 1, *counts[1:]]


def _half_the_batch(counts):
    return counts[: len(counts) // 2]


FAULTS = [
    ("gdelt.select", "query", _drop_a_row),
    ("tdrive.select", "query", _drop_a_row),
    ("gdelt.count", "count_many", _alter_a_count),
    ("gdelt.count", "count_many", _half_the_batch),
]


@pytest.mark.parametrize("cell,method,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, _, f in FAULTS])
def test_fault_is_not_correct(monkeypatch, capsys, cell, method, fault):
    _patch(monkeypatch, method, fault)
    rc = run.main(["--workload", cell, "--seed", "5", "--seconds", "2",
                   "--trace", "0", "--rehearse"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
