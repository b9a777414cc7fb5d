"""Distributed ingest: multiprocess converter parsing feeding the store.

Role parity: ``geomesa-jobs/.../mapreduce/ConverterInputFormat.scala:1``
(distributed ingest parse) and the tools' local multi-threaded ingest
(SURVEY.md §2.16/§2.19). Input files — or byte-range CHUNKS of one large
delimited file, split at line boundaries like Hadoop input splits — parse in
a process pool; each worker ships its FeatureTable back as Arrow IPC bytes
(zero shared state), and the parent bulk-appends into the store, compacting
once at the end. This is the parse half of bulk load; the sorted-store build
half is the store's normal compaction (LSM merge_build).
"""

from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor

__all__ = ["split_file", "parallel_ingest"]


def split_file(path: str, n_chunks: int) -> list[tuple[int, int]]:
    """Byte ranges [(offset, length)] cut at line boundaries.

    Mirrors Hadoop's FileSplit semantics: chunk i starts just after the
    first newline at-or-past ``i * size/n`` (chunk 0 at 0), ends where chunk
    i+1 starts — every line lands in exactly one chunk.
    """
    size = os.path.getsize(path)
    if n_chunks <= 1 or size == 0:
        return [(0, size)]
    approx = size // n_chunks
    cuts = [0]
    with open(path, "rb") as f:
        for i in range(1, n_chunks):
            target = i * approx
            if target <= cuts[-1]:
                continue
            f.seek(target)
            f.readline()  # skip to the next line boundary
            pos = f.tell()
            if pos >= size:
                break
            if pos > cuts[-1]:
                cuts.append(pos)
    cuts.append(size)
    return [(cuts[i], cuts[i + 1] - cuts[i]) for i in range(len(cuts) - 1)]


def _worker(args) -> bytes:
    """Parse one (file | chunk) with a freshly-built converter → Arrow IPC."""
    spec, path, offset, length = args
    # workers are fresh interpreters (spawn): keep them off the TPU — a
    # chip belongs to one process, the parent may hold it, and parsing is
    # host-side anyway
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass

    from geomesa_tpu.io.arrow import to_ipc_bytes

    table = _convert(spec, path, offset, length)
    return to_ipc_bytes(table)


def _convert(spec: dict, path: str, offset: int, length: int):
    from geomesa_tpu.schema.sft import parse_spec

    kind = spec["kind"]
    if offset or length is not None:
        with open(path, "rb") as f:
            f.seek(offset)
            data = f.read(length)
    else:
        data = open(path, "rb").read()

    if kind == "gdelt":
        from geomesa_tpu.convert.gdelt import gdelt_fast_table

        return gdelt_fast_table(data)
    sft = parse_spec(spec["sft_name"], spec["sft_spec"])
    if kind == "delimited":
        import io

        from geomesa_tpu.convert.delimited import DelimitedConverter

        conv = DelimitedConverter(
            sft, spec["fields"], delimiter=spec.get("delimiter", ","),
            id_field=spec.get("id_field"),
            error_mode=spec.get("error_mode", "skip"),
        )
        return conv.convert_path(io.BytesIO(data))
    if kind == "json":
        from geomesa_tpu.convert.json_converter import JsonConverter

        conv = JsonConverter(
            sft, spec["fields"], feature_path=spec.get("feature_path", "$"),
            id_field=spec.get("id_field"),
        )
        return conv.convert_str(data.decode("utf-8"))
    if kind == "xml":
        from geomesa_tpu.convert.xml_converter import XmlConverter

        conv = XmlConverter(
            sft, spec["fields"],
            feature_path=spec.get("feature_path", ".//feature"),
            id_field=spec.get("id_field"),
        )
        return conv.convert_str(data.decode("utf-8"))
    raise ValueError(f"unknown converter kind: {kind!r}")


def parallel_ingest(
    ds,
    type_name: str,
    converter_spec: dict,
    paths: list[str] | None = None,
    chunks_of: str | None = None,
    processes: int | None = None,
    fid_prefix: bool = True,
) -> int:
    """Ingest files (or chunks of one file) in parallel; returns rows written.

    ``converter_spec``: {"kind": "delimited"|"json"|"xml"|"gdelt",
    "sft_name", "sft_spec", "fields", ...} — everything a worker needs to
    rebuild the converter (workers share nothing). ``chunks_of``: split ONE
    large file into line-aligned byte ranges instead of per-file tasks.
    ``fid_prefix``: re-key each chunk's fids as ``<chunk>-<fid>`` so
    independently-parsed chunks can't collide.
    """
    from geomesa_tpu.io.arrow import from_ipc_bytes
    from geomesa_tpu.schema.sft import parse_spec

    if (paths is None) == (chunks_of is None):
        raise ValueError("pass exactly one of paths= or chunks_of=")
    if chunks_of is not None:
        n = processes or os.cpu_count() or 4
        tasks = [
            (converter_spec, chunks_of, off, ln)
            for off, ln in split_file(chunks_of, n)
        ]
        # chunk 0 carries the header if the format has one; delimited/gdelt
        # data files are headerless so every chunk parses standalone
    else:
        tasks = [(converter_spec, p, 0, None) for p in paths]

    sft = ds.get_schema(type_name)
    total = 0
    n_workers = min(processes or os.cpu_count() or 4, len(tasks)) or 1
    import multiprocessing as mp

    # spawn: fresh interpreters (no forked jax/pyarrow state)
    with ProcessPoolExecutor(
        max_workers=n_workers, mp_context=mp.get_context("spawn")
    ) as pool:
        for i, ipc in enumerate(pool.map(_worker, tasks)):
            table = from_ipc_bytes(sft, ipc)
            if fid_prefix:
                import numpy as np

                table = type(table)(
                    table.sft,
                    np.array([f"{i}-{f}" for f in table.fids], dtype=object),
                    table.columns,
                )
            total += ds.write(type_name, table)
    ds.compact(type_name)
    return total
