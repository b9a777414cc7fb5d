"""tpusync reconcile smoke — bench_gate leg 9 (ISSUE 18).

Green: export a host-roundtrip ledger from a REAL staged-select run
(two-phase count -> host sizing -> gather, forced by zeroing the
one-pass slot budget) and reconcile it against the ``@dispatch_budget``
declarations on the select paths via
``python -m geomesa_tpu.analysis --sync --reconcile`` — zero
divergence must exit 0.

Red: the same export with every dispatch count multiplied 5x must
exceed the static bounds and exit 1 naming the declaration — a gate
that cannot go red is not a gate.

The measurement half runs in THIS process (jax on the CPU mesh); each
analysis leg is a subprocess with GEOMESA_TPU_NO_JAX=1, exercising the
same CLI surface CI uses.
"""

import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _capture(tmp: str) -> str:
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

    cql = "BBOX(geom,-50,-40,50,40)"
    backends._ONE_PASS_MAX_SLOTS = 0  # force the staged two-phase select
    ds.query("pts", cql)              # compile the staged steps
    plantable.install()
    for _ in range(3):
        ds.query("pts", cql)
    doc = ledger_mod.table().export()

    staged = [e for e in doc["entries"]
              if e["queries"] and e["dispatches"] / e["queries"] >= 2.0]
    if not staged:
        print("[sync-smoke] FAIL: staged select did not measure >= 2 "
              "dispatches/query", file=sys.stderr)
        sys.exit(1)
    path = os.path.join(tmp, "ledger.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    print(f"[sync-smoke] captured {len(doc['entries'])} ledger entries "
          f"({len(staged)} staged multi-dispatch signature(s))")
    return path


def _reconcile(ledger_path: str) -> int:
    env = dict(os.environ, GEOMESA_TPU_NO_JAX="1")
    out = subprocess.run(
        [sys.executable, "-m", "geomesa_tpu.analysis", "--sync",
         "--rules", "S001", "--reconcile", ledger_path,
         "geomesa_tpu/store/backends.py", "geomesa_tpu/store/datastore.py"],
        capture_output=True, text=True, env=env)
    if out.stdout.strip():
        print(out.stdout.strip())
    return out.returncode


def main() -> None:
    os.chdir(os.path.join(os.path.dirname(__file__), ".."))
    with tempfile.TemporaryDirectory() as tmp:
        ledger_path = _capture(tmp)

        rc = _reconcile(ledger_path)
        if rc != 0:
            print(f"[sync-smoke] FAIL: live export diverged from the "
                  f"declared budgets (exit {rc})", file=sys.stderr)
            sys.exit(1)
        print("[sync-smoke] green: measured dispatch rates within "
              "declared budgets")

        with open(ledger_path, encoding="utf-8") as f:
            doc = json.load(f)
        for e in doc["entries"]:
            e["dispatches"] *= 5
        red_path = os.path.join(tmp, "ledger_red.json")
        with open(red_path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        rc = _reconcile(red_path)
        if rc != 1:
            print(f"[sync-smoke] FAIL: 5x dispatch rate was not flagged "
                  f"(exit {rc}, want 1)", file=sys.stderr)
            sys.exit(1)
        print("[sync-smoke] red: 5x dispatch rate flags the declaration")
    print("[sync-smoke] OK")


if __name__ == "__main__":
    main()
