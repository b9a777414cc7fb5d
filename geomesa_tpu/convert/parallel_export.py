"""Distributed export: query once, write partitioned output files in parallel.

Role parity: ``geomesa-tools`` distributed export
(``export/ExportJob.scala`` — SURVEY.md §2.17): a query's results are split
into chunks, each written as its own output file by a worker, with a manifest
tying the parts together. The reference fans out over MapReduce input splits;
here the scan already ran on the mesh, so the fan-out is over *writers* — the
query result is sliced into row ranges and a process pool encodes each slice
(Arrow IPC ships the slice to the worker; the worker owns one file). Output
formats reuse the single-file export encoders (csv/avro/parquet/orc/arrow).
"""

from __future__ import annotations

import json
import os
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

__all__ = ["parallel_export", "FORMATS"]

FORMATS = ("csv", "avro", "parquet", "orc", "arrow")


def _write_chunk(args) -> dict:
    """Worker: (sft spec, ipc bytes, path, fmt) → part metadata."""
    spec, ipc, path, fmt = args
    try:
        import jax

        # host-side work: stay off the TPU, which the parent may hold
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    from geomesa_tpu.io.arrow import from_ipc_bytes
    from geomesa_tpu.schema.sft import parse_spec

    sft = parse_spec(spec["name"], spec["spec"])
    table = from_ipc_bytes(sft, ipc)
    p = Path(path)
    if fmt == "arrow":
        p.write_bytes(ipc)
    elif fmt == "avro":
        from geomesa_tpu.io.avro import write_avro

        write_avro(table, str(p))
    elif fmt in ("parquet", "orc"):
        from geomesa_tpu.io.arrow import to_arrow

        at = to_arrow(table, dictionary_encode=False)
        if fmt == "parquet":
            import pyarrow.parquet as pq

            pq.write_table(at, str(p))
        else:
            import pyarrow.orc as po

            po.write_table(at, str(p))
    elif fmt == "csv":
        import pandas as pd

        rows = [table.record(i) for i in range(len(table))]
        cols = list(rows[0]) if rows else [a.name for a in sft.attributes]
        df = pd.DataFrame({c: [str(r.get(c)) for r in rows] for c in cols})
        df.to_csv(str(p), index=False)
    return {"file": p.name, "rows": len(table)}


def parallel_export(
    ds,
    type_name: str,
    query=None,
    out_dir: str | os.PathLike = "export",
    fmt: str = "parquet",
    workers: int | None = None,
    chunk_rows: int = 100_000,
) -> dict:
    """Run ``query`` and write its results as N part files in parallel.

    Returns the manifest (also written to ``<out_dir>/export.json``):
    ``{"type", "format", "rows", "parts": [{"file", "rows"}, ...]}``.
    """
    if fmt not in FORMATS:
        raise ValueError(f"format must be one of {FORMATS}: {fmt!r}")
    if workers is not None and workers < 1:
        raise ValueError(f"workers must be >= 1: {workers}")
    if Path(out_dir).is_file():
        raise ValueError(f"output dir is an existing file: {out_dir}")
    from geomesa_tpu.io.arrow import to_ipc_bytes

    r = ds.query(type_name, query)
    table = r.table
    sft = table.sft
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    n = len(table)
    spec = {"name": sft.name, "spec": sft.to_spec()}

    import numpy as np

    ext = "ipc" if fmt == "arrow" else fmt
    tasks = []
    for k, lo in enumerate(range(0, max(n, 1), chunk_rows)):
        hi = min(lo + chunk_rows, n)
        chunk = table.take(np.arange(lo, hi))
        tasks.append(
            (spec, to_ipc_bytes(chunk), str(out / f"part-{k:05d}.{ext}"), fmt)
        )

    n_workers = min(workers or os.cpu_count() or 4, len(tasks)) or 1
    if n_workers == 1:
        parts = [_write_chunk(t) for t in tasks]
    else:
        import multiprocessing as mp

        with ProcessPoolExecutor(
            max_workers=n_workers, mp_context=mp.get_context("spawn")
        ) as pool:
            parts = list(pool.map(_write_chunk, tasks))

    manifest = {
        "type": type_name,
        "format": fmt,
        "rows": int(sum(p["rows"] for p in parts)),
        "parts": parts,
    }
    (out / "export.json").write_text(json.dumps(manifest, indent=2))
    return manifest
