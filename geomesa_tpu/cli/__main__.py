"""Command-line tools: the ``geomesa-tools`` role (SURVEY.md §2.17).

Command families mirror the reference's JCommander runner
(``geomesa-tools/.../Runner.scala:47``): schema CRUD, ingest, export
(csv/json/arrow/bin), explain, stats. State lives in a ``--catalog`` directory
(:mod:`geomesa_tpu.store.persistence`).

    python -m geomesa_tpu.cli create-schema -c /tmp/cat -n gdelt --spec '...'
    python -m geomesa_tpu.cli ingest -c /tmp/cat -n gdelt --converter gdelt f.tsv
    python -m geomesa_tpu.cli export -c /tmp/cat -n gdelt -q "BBOX(geom,...)" --format csv
    python -m geomesa_tpu.cli explain -c /tmp/cat -n gdelt -q "..."
    python -m geomesa_tpu.cli stats-analyze -c /tmp/cat -n gdelt
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np


def _load(args):
    from geomesa_tpu.store import persistence

    if not (Path(args.catalog) / persistence.MANIFEST).exists():
        from geomesa_tpu.store.datastore import DataStore

        return DataStore(backend=args.backend)
    return persistence.load(args.catalog, backend=args.backend)


def _save(ds, args):
    from geomesa_tpu.store import persistence

    persistence.save(ds, args.catalog)


def cmd_version(args):
    import geomesa_tpu

    print(f"geomesa-tpu {geomesa_tpu.__version__}")


def cmd_create_schema(args):
    ds = _load(args)
    ds.create_schema(args.name, args.spec)
    _save(ds, args)
    print(f"created schema {args.name!r}")


def cmd_get_type_names(args):
    ds = _load(args)
    for n in ds.list_schemas():
        print(n)


def cmd_describe_schema(args):
    ds = _load(args)
    sft = ds.get_schema(args.name)
    for a in sft.attributes:
        star = "*" if a.name == sft.default_geom else " "
        opts = " " + ",".join(f"{k}={v}" for k, v in a.options.items()) if a.options else ""
        print(f"{star}{a.name:<24}{a.type.value}{opts}")
    if sft.user_data:
        print("user-data:", json.dumps(sft.user_data))
    print(f"features: {ds.stats_count(args.name)}")


def cmd_update_schema(args):
    ds = _load(args)
    kw = args.keywords.split(",") if args.keywords else None
    sft = ds.update_schema(
        args.name, add=args.add or None, keywords=kw, rename_to=args.rename_to
    )
    _save(ds, args)
    print(f"updated schema {sft.name!r}: {sft.to_spec()}")


def cmd_delete_schema(args):
    ds = _load(args)
    ds.delete_schema(args.name)
    _save(ds, args)
    print(f"deleted schema {args.name!r}")


def cmd_delete_features(args):
    ds = _load(args)
    if args.fids is not None:
        fids = [f for f in args.fids.split(",") if f]
        if not fids:
            raise SystemExit("--fids must name at least one feature id")
        n = ds.delete_features(args.name, fids)
    else:
        r = ds.query(args.name, args.cql)
        n = ds.delete_features(args.name, r.table.fids.tolist())
    _save(ds, args)
    print(f"deleted {n} features from {args.name!r}")


def cmd_ingest(args):
    from geomesa_tpu.convert.delimited import DelimitedConverter, EvaluationContext

    ds = _load(args)
    if args.converter == "gdelt":
        from geomesa_tpu.convert.gdelt import gdelt_converter, gdelt_sft

        if args.name not in ds.list_schemas():
            ds.create_schema(gdelt_sft(args.name))
        conv = gdelt_converter(ds.get_schema(args.name))
    elif args.converter and args.converter != "delimited":
        # config file path, predefined dataset, or schema-inferring type name
        from geomesa_tpu.convert.config import load_converter

        existing = (
            ds.get_schema(args.name) if args.name in ds.list_schemas() else None
        )
        conv = load_converter(args.converter, sft=existing, type_name=args.name)
        if conv.sft is None:
            conv.infer_from(args.files[0])
        if existing is None:
            ds.create_schema(conv.sft)
        elif [(a.name, a.type) for a in conv.sft.attributes] != [
            (a.name, a.type) for a in existing.attributes
        ]:
            # structural converters (gpx/osm/predefined) define their own
            # layout — refuse to write it into a differently-shaped schema
            raise SystemExit(
                f"converter {args.converter!r} produces "
                f"({conv.sft.to_spec()}) which does not match the existing "
                f"schema {args.name!r} ({existing.to_spec()})"
            )
    else:
        sft = ds.get_schema(args.name)
        fields = dict(kv.split("=", 1) for kv in (args.field or []))
        conv = DelimitedConverter(
            sft,
            fields=fields,
            id_field=args.id_field,
            delimiter="\t" if args.format == "tsv" else ",",
            header=args.header,
            error_mode=args.error_mode,
        )
    ctx = EvaluationContext()
    # convert all files first, then a single write: each write rebuilds all
    # indexes + device state over the cumulative table, so per-file writes
    # would be quadratic in file count
    tables = []
    for fi, path in enumerate(args.files):
        t = conv.convert_path(path, ctx)
        if conv.id_field is None and len(args.files) > 1:
            # row-number fids collide across files; qualify with the file index
            t.fids = np.asarray([f"{fi}.{f}" for f in t.fids], dtype=object)
        tables.append(t)
    if len(tables) == 1:
        total = ds.write(args.name, tables[0])
    else:
        from geomesa_tpu.schema.columnar import FeatureTable

        total = ds.write(args.name, FeatureTable.concat(tables))
    _save(ds, args)
    print(f"ingested {total} features ({ctx.failure} failed) into {args.name!r}")


def _query_of(args):
    from geomesa_tpu.planning.planner import Query

    hints = {}
    if getattr(args, "hints", None):
        hints = json.loads(args.hints)
    if getattr(args, "srs", None):
        # output reprojection (the CLI export --srs role): validate before
        # the scan so a bad code fails fast
        from geomesa_tpu.utils.crs import get_crs

        try:
            get_crs(args.srs)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        hints["crs"] = args.srs
    return Query(
        filter=args.cql,
        limit=getattr(args, "max", None),
        start_index=getattr(args, "start_index", None),
        hints=hints,
        properties=args.attributes.split(",") if getattr(args, "attributes", None) else None,
    )


def cmd_export(args):
    ds = _load(args)
    if args.parallel is not None:
        # distributed export (ExportJob role): N part files + manifest
        from geomesa_tpu.convert.parallel_export import FORMATS, parallel_export

        if args.parallel < 1:
            raise SystemExit("--parallel requires N >= 1 workers")
        if args.format not in FORMATS:
            raise SystemExit(
                f"--parallel supports formats: {', '.join(FORMATS)}"
            )
        if args.output is None:
            raise SystemExit("--parallel requires -o OUTPUT_DIR")
        if Path(args.output).is_file():
            raise SystemExit(f"-o {args.output!r} is an existing file; "
                             "--parallel writes a directory")
        m = parallel_export(
            ds, args.name, _query_of(args), args.output,
            fmt=args.format, workers=args.parallel,
        )
        print(f"exported {m['rows']} features in {len(m['parts'])} parts",
              file=sys.stderr)
        return
    r = ds.query(args.name, _query_of(args))
    if args.format in ("shp", "leaflet") and r.table.sft.geom_field is None:
        raise SystemExit(f"{args.format} export requires the geometry column "
                         "(projection dropped it)")
    if args.format == "shp":
        # no pre-opened sink: write_shapefile owns the .shp/.shx/.dbf set,
        # and a validation error must not truncate an existing output
        if args.output is None or not args.output.endswith(".shp"):
            raise SystemExit("shp export requires -o OUTPUT.shp")
        from geomesa_tpu.convert.shapefile import write_shapefile

        write_shapefile(r.table, args.output)
        print(f"exported {r.count} features", file=sys.stderr)
        return
    out = sys.stdout.buffer if args.output is None else open(args.output, "wb")
    try:
        if args.format == "csv":
            import pandas as pd

            rows = r.records()
            df = {c: [str(rec.get(c)) for rec in rows] for c in (rows[0] if rows else {})}
            pd.DataFrame(df).to_csv(out, index=False)
        elif args.format == "json":
            for rec in r.records():
                out.write((json.dumps({k: str(v) for k, v in rec.items()}) + "\n").encode())
        elif args.format == "arrow":
            from geomesa_tpu.io.arrow import to_ipc_bytes

            out.write(to_ipc_bytes(r.table))
        elif args.format == "bin":
            from geomesa_tpu.store.reduce import bin_encode as _bin_encode

            out.write(_bin_encode(r.table, {"track": args.bin_track, "sort": True}))
        elif args.format == "avro":
            from geomesa_tpu.io.avro import write_avro

            write_avro(r.table, out)
        elif args.format in ("parquet", "orc"):
            from geomesa_tpu.io.arrow import to_arrow

            at = to_arrow(r.table, dictionary_encode=False)
            if args.format == "parquet":
                import pyarrow.parquet as pq

                pq.write_table(at, out)
            else:
                import pyarrow.orc as po

                po.write_table(at, out)
        elif args.format == "gml":
            from geomesa_tpu.io.gml import to_gml

            out.write(to_gml(r.table))
        elif args.format == "leaflet":
            from geomesa_tpu.jupyter import map_html

            out.write(map_html(r.table).encode("utf-8"))
        else:
            raise SystemExit(f"unknown format: {args.format}")
    finally:
        if args.output is not None:
            out.close()
    print(f"exported {r.count} features", file=sys.stderr)


def cmd_explain(args):
    ds = _load(args)
    print(ds.explain(args.name, args.cql))


def cmd_sql(args):
    """Run one SQL statement against the catalog (the spark-sql shell /
    GeoMesaRelation role) and print csv or json-lines rows."""
    import json as _json

    from geomesa_tpu.sql.engine import SqlError, sql

    ds = _load(args)
    try:
        res = sql(ds, args.query)
    except SqlError as e:
        raise SystemExit(f"sql error: {e}")
    names = list(res.columns)
    if args.format == "json":
        for row in res.rows():  # rows() already unwraps np.generic
            print(_json.dumps(dict(zip(names, row)), default=str))
        return
    import csv as _csv

    w = _csv.writer(sys.stdout)
    w.writerow(names)
    for row in res.rows():
        w.writerow(["" if v is None else v for v in row])


def cmd_stats_analyze(args):
    ds = _load(args)
    sft = ds.get_schema(args.name)
    print(f"count: {ds.stats_count(args.name)}")
    for a in sft.attributes:
        if a.type.is_geometry:
            continue
        try:
            lo, hi = ds.stats_bounds(args.name, a.name)
            card = ds.stats_cardinality(args.name, a.name)
            print(f"{a.name}: bounds=[{lo}, {hi}] cardinality~{card:.0f}")
        except Exception:
            pass


def cmd_stats_count(args):
    ds = _load(args)
    print(ds.stats_count(args.name, args.cql, exact=not args.estimate))


def cmd_stats_top_k(args):
    ds = _load(args)
    for v, c in ds.stats_top_k(args.name, args.attribute, args.k):
        print(f"{v}\t{c}")


def cmd_stats_histogram(args):
    ds = _load(args)
    h = ds.stats_histogram(args.name, args.attribute)
    if h is None:
        raise SystemExit(f"no histogram for {args.attribute!r} (non-numeric?)")
    step = (h.hi - h.lo) / h.bins
    for i in range(0, h.bins, max(1, h.bins // args.bins)):
        lo = h.lo + i * step
        c = int(h.counts[i : i + max(1, h.bins // args.bins)].sum())
        print(f"[{lo:.4g}, {lo + step * max(1, h.bins // args.bins):.4g}): {c}")


def cmd_manage_partitions(args):
    """``manage-partitions`` (geomesa-tools role, SURVEY.md §2.17): list the
    catalog's persisted partitions per type, or delete one partition's rows
    (drop + re-save, the reference's delete-partition semantics)."""
    import json as _json

    from geomesa_tpu.store import persistence

    mpath = Path(args.catalog) / persistence.MANIFEST
    if not mpath.exists():
        raise SystemExit(f"no catalog manifest under {args.catalog!r}")
    manifest = _json.loads(mpath.read_text())

    if args.action == "list":
        meta = manifest["types"].get(args.name)
        if meta is None:
            raise SystemExit(f"unknown type: {args.name!r}")
        print(f"scheme: {meta.get('scheme', 'flat')}  rows: {meta['count']}")
        for f in meta["files"]:
            size = (Path(args.catalog) / args.name / f["file"]).stat().st_size
            print(f"  {f['partition']:<24} {f['rows']:>10} rows  "
                  f"{size:>10} bytes  {f['file']}")
        return

    if args.action == "delete":
        if not args.partition:
            raise SystemExit("delete requires --partition KEY")
        meta = manifest["types"].get(args.name)
        if meta is None:
            raise SystemExit(f"unknown type: {args.name!r}")
        ds = _load(args)
        st = ds._state(args.name)
        if st.table is None or len(st.table) == 0:
            raise SystemExit("type holds no rows")
        # membership follows the manifest's recorded scheme — the same
        # partitioning `list` displays — not the schema's current user-data
        from geomesa_tpu.store.partitions import scheme_from_spec

        scheme = scheme_from_spec(meta.get("scheme", "flat"))
        keys = scheme.keys(st.sft, st.table)
        keep = keys != args.partition
        dropped = int((~keep).sum())
        if dropped == 0:
            raise SystemExit(f"no rows in partition {args.partition!r}")
        # drop by ROW, not by fid: duplicate fids across ingests must not
        # pull rows out of other partitions
        ds._rebuild(st, st.table.take(np.nonzero(keep)[0]))
        _save(ds, args)
        print(f"deleted partition {args.partition!r}: {dropped} rows")
        return

    raise SystemExit(f"unknown action: {args.action!r}")


def cmd_wal(args):
    """Offline WAL inspection (no store open, no lock taken): what is in
    the journal, what the manifest already covers, and how many acked
    records a recovery would replay."""
    import json as _json

    from geomesa_tpu.store import wal as walmod
    from geomesa_tpu.stream.journal import JournalBus

    bus = JournalBus(args.dir, partitions=1)
    stamps: dict[str, int] = {}
    global_floor = 0
    if args.catalog:
        from geomesa_tpu.store import persistence

        mpath = Path(args.catalog) / persistence.MANIFEST
        if mpath.exists():
            wstamp = _json.loads(mpath.read_text()).get("wal") or {}
            global_floor = int(wstamp.get("seq", 0))
            stamps = {str(k): int(v)
                      for k, v in (wstamp.get("topics") or {}).items()}
    topics = [t for t in bus.topics()
              if t == walmod.SCHEMA_TOPIC or t.startswith("wal.t.")]
    report = {"dir": args.dir, "topics": [], "unreplayed_tail": 0}
    for topic in sorted(topics):
        records = seq_lo = seq_hi = tail = 0
        by_op: dict[str, int] = {}
        for _s, _e, payload in bus.iter_records(topic):
            try:
                hdr, _ = walmod.decode_record(payload)
            except (ValueError, KeyError):
                continue
            seq = int(hdr.get("seq", 0))
            records += 1
            seq_lo = seq if seq_lo == 0 else min(seq_lo, seq)
            seq_hi = max(seq_hi, seq)
            by_op[hdr.get("op", "?")] = by_op.get(hdr.get("op", "?"), 0) + 1
            if seq > stamps.get(topic, global_floor):
                tail += 1
        report["topics"].append({
            "topic": topic,
            "type": walmod.type_for(topic),
            "records": records,
            "ops": by_op,
            "seq_range": [seq_lo, seq_hi],
            "head_bytes": bus.head_offset(topic),
            "committed_bytes": bus.committed_offset(topic),
            "manifest_floor": stamps.get(topic),
            "unreplayed_tail": tail,
        })
        report["unreplayed_tail"] += tail
    bus.close()
    if args.json:
        print(_json.dumps(report, indent=2))
        return
    print(f"WAL {args.dir}")
    for t in report["topics"]:
        ops = ",".join(f"{k}:{v}" for k, v in sorted(t["ops"].items()))
        floor = t["manifest_floor"]
        print(f"  {t['topic']:<32} records={t['records']:<6} "
              f"seq={t['seq_range'][0]}..{t['seq_range'][1]} "
              f"head={t['head_bytes']} committed={t['committed_bytes']} "
              f"floor={'-' if floor is None else floor} "
              f"tail={t['unreplayed_tail']}  [{ops}]")
    if args.catalog:
        print(f"unreplayed tail (records a recovery would replay): "
              f"{report['unreplayed_tail']}")


def cmd_serve(args):
    from geomesa_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # a long-lived server keeps what it compiles
    if getattr(args, "recover", False) or getattr(args, "wal", None):
        from geomesa_tpu.store.datastore import DataStore

        ds = DataStore.open(args.catalog, backend=args.backend,
                            recover=True, wal_dir=args.wal)
    else:
        ds = _load(args)
    from geomesa_tpu.web import serve

    provider = None
    if args.auths_header:
        from geomesa_tpu.security.auth import HeaderAuthorizationsProvider

        provider = HeaderAuthorizationsProvider(args.auths_header)
    journal = None
    if args.journal:
        from geomesa_tpu.stream.journal import JournalBus

        journal = JournalBus(args.journal)
    registry = None
    if args.registry:
        from geomesa_tpu.stream.confluent import SchemaRegistry

        registry = SchemaRegistry()
    admission = None
    if args.admit or args.admit_rate is not None:
        from geomesa_tpu.serving.admission import AdmissionController

        admission = AdmissionController(
            rate_qps=args.admit_rate,
            metrics=getattr(ds, "metrics", None))
    serve(ds, host=args.host, port=args.port, auth_provider=provider,
          journal=journal, schema_registry=registry, admission=admission,
          coalesce_ms=args.coalesce_ms)


def cmd_compact(args):
    ds = _load(args)
    ds.compact(args.name)
    _save(ds, args)
    print(f"compacted {args.name!r}: {ds.stats_count(args.name)} rows in main tier")


def cmd_obs_flight(args):
    """Pull a server's query-audit flight recorder (``GET
    /api/obs/flight``) and render it — the operator's first stop after a
    burn-rate alert (docs/operations.md runbook)."""
    import urllib.parse
    import urllib.request

    qp = {"limit": args.limit}
    # server-side filters (the recorder applies them before the limit)
    if getattr(args, "tenant", None):
        qp["tenant"] = args.tenant
    if getattr(args, "type", None):
        qp["type"] = args.type
    if getattr(args, "anomalies", False):
        qp["anomalies"] = 1
    url = (args.url.rstrip("/") + "/api/obs/flight?"
           + urllib.parse.urlencode(qp))
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    print(f"flight recorder: {doc['record_count']} recorded, "
          f"{doc['dump_count']} anomaly dumps"
          + (f", last dump {doc['last_dump']}" if doc.get("last_dump") else ""))
    print(f"{'ts':>14s} {'op':<12s} {'type':<14s} {'tenant':<12s} "
          f"{'ms':>9s} {'rows':>7s} {'flags':<18s} plan")
    for rec in doc.get("records", []):
        flags = ",".join(rec.get("anomalies") or ()) or "-"
        members = rec.get("members") or []
        extra = ""
        if members:
            bad = sum(1 for m in members if m[1] != "ok")
            extra = f" [{len(members) - bad}/{len(members)} members ok]"
        print(f"{rec['ts']:>14.3f} {rec['op']:<12s} {rec['type_name']:<14s} "
              f"{(rec.get('tenant') or '-'):<12s} "
              f"{rec['latency_ms']:>9.2f} {rec['rows']:>7d} {flags:<18s} "
              f"{rec['plan'][:60]}{extra}")


def cmd_obs_costs(args):
    """Pull a server's per-(type, plan-signature) observed-cost table
    (``GET /api/obs/costs``) — p50/p95 device-ms and wall-ms per plan
    shape, the capacity-planning companion to ``obs flight``
    (docs/observability.md § Device telemetry & cost profiles)."""
    import urllib.request

    url = args.url.rstrip("/") + f"/api/obs/costs?limit={args.limit}"
    if getattr(args, "member", None) is not None:
        url += f"&member={args.member}"
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    entries = doc.get("entries", [])
    print(f"cost profiles: {doc.get('entry_count', len(entries))} "
          f"(type, plan-signature) entries")
    print(f"{'type':<14s} {'signature':<28s} {'n':>6s} {'prof':>5s} "
          f"{'wall p50':>9s} {'wall p95':>9s} {'dev p50':>8s} "
          f"{'rows p50':>9s} {'scan B p50':>11s}")
    for e in entries:
        print(f"{e['type']:<14s} {e['signature']:<28s} {e['count']:>6d} "
              f"{e['profiled']:>5d} {e['wall_ms_p50']:>9.2f} "
              f"{e['wall_ms_p95']:>9.2f} {e['device_ms_p50']:>8.2f} "
              f"{e['rows_p50']:>9.1f} {int(e['bytes_scanned_p50']):>11d}")
    members = doc.get("members") or []
    if members:
        print("\nper-member observed cost (federated fan-out legs):")
        print(f"{'member':>6s} {'store':<22s} {'type':<14s} {'op':<12s} "
              f"{'n':>6s} {'wall p50':>9s} {'wall p95':>9s}")
        for m in members:
            print(f"{m['member']:>6d} {m['store']:<22s} {m['type']:<14s} "
                  f"{m['op']:<12s} {m['count']:>6d} "
                  f"{m['wall_ms_p50']:>9.2f} {m['wall_ms_p95']:>9.2f}")
    cal = doc.get("calibration") or {}
    rows = cal.get("entries", [])
    if rows:
        overall = cal.get("overall_mean_abs_rel_err")
        print(f"\ncalibration (predicted vs actual): {len(rows)} plan "
              f"shapes, overall MAPE "
              + (f"{overall:.1%}" if overall is not None else "n/a"))
        print(f"{'type':<14s} {'signature':<28s} {'n':>6s} {'MAPE':>7s} "
              f"{'bias':>7s} {'last pred':>10s} {'last act':>10s}")
        for e in rows:
            print(f"{e['type']:<14s} {e['signature']:<28s} "
                  f"{e['count']:>6d} {e['mean_abs_rel_err']:>6.1%} "
                  f"{e['mean_signed_rel_err']:>+6.1%} "
                  f"{e['last_predicted_ms']:>10.2f} "
                  f"{e['last_actual_ms']:>10.2f}")


def cmd_obs_tenants(args):
    """Pull a server's per-tenant usage accounting (``GET
    /api/obs/tenants``): rolling-window counters, heavy-hitter query
    shapes, per-tenant SLO burn — the capacity-attribution surface
    (docs/observability.md § Usage metering & workload replay)."""
    import urllib.request

    url = args.url.rstrip("/") + f"/api/obs/tenants?limit={args.limit}"
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    print(f"tenants: {doc['tenant_count']} tracked, "
          f"{doc['observe_count']} queries metered (top-K={doc['k']})")
    print(f"{'tenant':<20s} {'q (5m)':>8s} {'rows (5m)':>10s} "
          f"{'wall ms (5m)':>13s} {'dev ms (5m)':>12s} {'q (life)':>9s} "
          f"{'burn 5m':>8s}")
    for t in doc.get("tenants", []):
        w = t["windows"].get("5m", {})
        life = t["lifetime"]
        slo = t.get("slo", {})
        print(f"{t['tenant']:<20s} {w.get('queries', 0):>8d} "
              f"{w.get('rows', 0):>10d} {w.get('wall_ms', 0.0):>13.1f} "
              f"{w.get('device_ms', 0.0):>12.1f} {life['queries']:>9d} "
              f"{slo.get('burn_rate_5m', 0.0):>8.2f}")
    hitters = doc.get("heavy_hitters", [])
    if hitters:
        print(f"\nheavy hitters (wall-ms, overestimate <= error):")
        print(f"{'tenant':<20s} {'type':<14s} {'signature':<28s} "
              f"{'wall ms':>10s} {'err ms':>8s}")
        for h in hitters:
            print(f"{h['tenant']:<20s} {h['type']:<14s} "
                  f"{h['signature']:<28s} {h['wall_ms']:>10.1f} "
                  f"{h['error_ms']:>8.1f}")


def cmd_obs_audit(args):
    """Pull a server's continuous correctness auditor (``GET
    /api/obs/audit``): per-kind checked/passed/diverged/abstained
    counters, recent divergences with repro-bundle paths, invariant-
    sweep results — the divergence-triage entry point
    (docs/operations.md runbook)."""
    import urllib.request

    url = args.url.rstrip("/") + f"/api/obs/audit?limit={args.limit}"
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    print(f"auditor: rate={doc['rate']} queue={doc['queue_depth']} "
          f"dropped={doc['dropped']} errors={doc['errors']} "
          f"bundles={doc['bundles_written']}"
          + (f" -> {doc['bundle_dir']}" if doc.get("bundle_dir") else ""))
    print(f"{'kind':<22s} {'checked':>8s} {'passed':>8s} "
          f"{'diverged':>9s} {'abstained':>10s}")
    for kind, c in sorted(doc.get("checks", {}).items()):
        print(f"{kind:<22s} {c['checked']:>8d} {c['passed']:>8d} "
              f"{c['diverged']:>9d} {c['abstained']:>10d}")
    for d in doc.get("divergences", []):
        print(f"\nDIVERGED [{d['kind']}] {d['type_name']}: {d['detail']}")
        if d.get("minimized"):
            print(f"  minimized: {d['minimized']}")
        if d.get("bundle_path"):
            print(f"  bundle:    {d['bundle_path']} "
                  f"(geomesa-tpu replay --bundle)")
    sweeps = doc.get("sweeps", {})
    if sweeps:
        print("\ninvariant sweeps:")
        for name, r in sorted(sweeps.items()):
            state = ("VIOLATED" if r.get("violations")
                     else "abstained" if r.get("abstained")
                     and not r.get("checked") else "ok")
            print(f"  {name:<18s} checked={r.get('checked', 0):<5d} "
                  f"abstained={r.get('abstained', 0):<4d} {state}")
            for v in r.get("violations", [])[:4]:
                print(f"    ! {v}")


def cmd_obs_lens(args):
    """Pull a server's retained profiling plane (``GET /api/obs/lens``):
    per-(type, plan-signature) live-window quantiles, retained latency
    history, trace exemplars, and the regression sentinel's alarms —
    the "since when is this signature slow" surface
    (docs/observability.md § Query lens & host-roundtrip ledger)."""
    import urllib.parse
    import urllib.request

    qp = {"limit": args.limit, "window": args.window}
    if getattr(args, "type", None):
        qp["type"] = args.type
    url = (args.url.rstrip("/") + "/api/obs/lens?"
           + urllib.parse.urlencode(qp))
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    sent = doc.get("sentinel", {})
    print(f"query lens: {doc['series']} series, "
          f"{doc['observe_count']} observed; sentinel: "
          f"{len(sent.get('alarms', []))} active alarms, "
          f"{sent.get('regressions_total', 0)} regressions total")
    print(f"{'type':<14s} {'signature':<28s} {'n':>6s} {'p50':>8s} "
          f"{'p95':>8s} {'p99':>8s} {'max':>8s} {'disp':>6s} exemplar")
    for e in doc.get("entries", []):
        w = e["window"]
        ex = e.get("exemplars") or []
        tid = ex[0]["trace_id"][:16] if ex else "-"
        print(f"{e['type']:<14s} {e['signature']:<28s} {w['count']:>6d} "
              f"{w['p50_ms']:>8.2f} {w['p95_ms']:>8.2f} "
              f"{w['p99_ms']:>8.2f} {w['max_ms']:>8.2f} "
              f"{w['dispatches']:>6d} {tid}")
    for a in sent.get("alarms", []):
        print(f"\nREGRESSED [{a['cause']}] {a['type']} {a['signature']}: "
              f"live {a['live_ms']:.2f} ms vs ref {a['ref_ms']:.2f} ms "
              f"({a['factor']:.2f}x, n={a['live_count']})")


def cmd_obs_stream_report(args):
    """Pull a server's standing-query scale report (``GET
    /api/obs/stream``): per topic, subscriptions ranked by scan-cost
    share with delivery p50/p99, on-time/late accounting, and a
    chunk-trace exemplar each, plus the capacity section (occupancy,
    churn, predicted next bucket-crossing recompile, HBM bytes per
    subscription ×1M) and the backlog sentinel's alarms —
    docs/operations.md § Standing-query health."""
    import urllib.parse
    import urllib.request

    qp = {"limit": args.limit, "window": args.window}
    if getattr(args, "topic", None):
        qp["topic"] = args.topic
    url = (args.url.rstrip("/") + "/api/obs/stream?"
           + urllib.parse.urlencode(qp))
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    sent = doc.get("sentinel", {})
    print(f"stream lens: {doc['observe_count']} observations; sentinel: "
          f"{len(sent.get('alarms', []))} active alarms, "
          f"{sent.get('backlogs_total', 0)} backlogs total")
    for t in doc.get("topics", []):
        cap = t.get("capacity", {})
        print(f"\ntopic {t['topic']}: {t['series']} subscriptions tracked")
        if cap.get("observed"):
            nxt = cap.get("next_bucket_crossing", {})
            eta = nxt.get("eta_s")
            print(f"  capacity {cap['active']}/{cap['capacity']} "
                  f"(occupancy {cap['occupancy']:.0%}), "
                  f"churn {cap['churn_per_s']:.3g}/s; next recompile in "
                  f"{nxt.get('adds_until_grow')} adds"
                  + (f" (~{eta:.0f}s)" if eta is not None else "")
                  + f"; HBM {cap['hbm_bytes_per_subscription']} B/sub "
                  f"({cap['hbm_bytes_at_1m'] / 1e6:.1f} MB at 1M)")
            if cap.get("dropped_rows"):
                print(f"  dropped: {cap['dropped_rows']} rows in "
                      f"{cap['dropped_chunks']} poisoned chunks")
        print(f"  {'sub':<8s} {'cost%':>6s} {'hits':>8s} {'deliv':>6s} "
              f"{'p50':>8s} {'p99':>8s} {'on-time':>8s} exemplar")
        for e in t.get("subscriptions", []):
            w = e["window"]
            ex = e.get("exemplars") or []
            tid = ex[0]["trace_id"][:16] if ex else "-"
            frac = w.get("on_time_fraction")
            print(f"  {e['subscription']:<8s} "
                  f"{e['cost_share'] * 100:>5.1f}% {e['hit_rows']:>8d} "
                  f"{e['deliveries']:>6d} {w['p50_ms']:>8.2f} "
                  f"{w['p99_ms']:>8.2f} "
                  f"{(f'{frac:.1%}' if frac is not None else '-'):>8s} "
                  f"{tid}")
        other = t.get("other")
        if other:
            print(f"  other: {other['series']} evicted series, "
                  f"cost {other['cost']:.1f}, {other['hit_rows']} hits")
    for a in sent.get("alarms", []):
        print(f"\nBACKLOG [{a['cause']}] {a['topic']}: "
              f"{a['value']:.6g} over {a['threshold']:.6g} "
              f"(scan_lag={a['scan_lag']}, freshness={a['freshness_ms']} ms, "
              f"burn={a['burn_rate']})")


def cmd_obs_fusion(args):
    """Pull a server's host-roundtrip fusion-opportunity report (``GET
    /api/obs/fusion``): plan signatures ranked by host-choreography
    share — the work list for whole-plan device compilation
    (docs/observability.md § fusion-report workflow)."""
    import urllib.request

    url = args.url.rstrip("/") + f"/api/obs/fusion?limit={args.limit}"
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    entries = doc.get("entries", [])
    print(f"fusion report: {len(entries)} (type, plan-signature) entries "
          f"ranked by host-choreography share")
    print(f"{'type':<14s} {'signature':<28s} {'n':>6s} {'host%':>6s} "
          f"{'disp/q':>7s} {'sync/q':>7s} {'gap ms':>9s} {'sync ms':>9s} "
          f"{'wall ms':>9s}")
    for e in entries:
        print(f"{e['type']:<14s} {e['signature']:<28s} {e['queries']:>6d} "
              f"{e['host_share'] * 100:>5.1f}% "
              f"{e['dispatches_per_query']:>7.2f} "
              f"{e['syncs_per_query']:>7.2f} {e['host_gap_ms']:>9.2f} "
              f"{e['sync_ms']:>9.2f} {e['wall_ms']:>9.2f}")


def cmd_obs_ledger_export(args):
    """Pull a server's raw roundtrip-ledger rollup (``GET
    /api/obs/ledger?format=json``) in the stable reconcile-export schema
    and write it to ``--output`` (stdout by default) — the measured side
    of ``python -m geomesa_tpu.analysis --sync --reconcile``."""
    import urllib.request

    url = args.url.rstrip("/") + "/api/obs/ledger?format=json"
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    text = json.dumps(doc, indent=2)
    if args.output and args.output != "-":
        with open(args.output, "w", encoding="utf-8") as f:
            f.write(text + "\n")
        print(f"wrote {len(doc.get('entries', []))} ledger entries "
              f"(schema_version={doc.get('schema_version')}) to "
              f"{args.output}", file=sys.stderr)
    else:
        print(text)


def cmd_obs_shards(args):
    """Pull a server's shard-routing state (``GET /api/obs/shards``):
    generation, per-shard ownership, LIVE migration records (state,
    rows shipped/replayed, dual-ledger size), coverage violations, and
    the process-wide migration counters — the elasticity triage surface
    (docs/operations.md § Migration triage)."""
    import urllib.request

    url = args.url.rstrip("/") + "/api/obs/shards"
    with urllib.request.urlopen(url, timeout=args.timeout) as r:  # noqa: S310
        doc = json.load(r)
    if args.json:
        print(json.dumps(doc, indent=2))
        return
    counters = doc.get("migration_counters", {})
    print("migrations: " + "  ".join(
        f"{k}={v}" for k, v in counters.items()))
    if "shard_member" not in doc:
        print("not a sharded federation (single member)")
        return
    print(f"generation {doc.get('generation')}  "
          f"members={doc.get('members')}  "
          f"inflight_writes={doc.get('inflight_writes', 0)}")
    owners: dict = {}
    for s, m in enumerate(doc.get("shard_member", [])):
        owners.setdefault(m, []).append(s)
    for m in sorted(owners, key=str):
        print(f"  member {m}: shards {owners[m]}")
    migs = doc.get("migrations", [])
    if migs:
        print(f"{len(migs)} live migration(s):")
        for mig in migs:
            print(f"  shard {mig['shard']}: {mig['src']} -> {mig['dst']} "
                  f"state={mig['state']} shipped={mig['rows_shipped']} "
                  f"replayed={mig['rows_replayed']} "
                  f"dual_fids={mig['dual_fids']}")
    bad = doc.get("coverage_violations", [])
    if bad:
        print(f"COVERAGE VIOLATIONS: {bad}")


def cmd_replay(args):
    """Replay a captured workload (``GEOMESA_TPU_WORKLOAD_DIR`` capture)
    against a catalog or a live server and print the recorded-vs-replayed
    report — the replay-before-deploy workflow (docs/operations.md).
    ``--bundle`` instead re-executes one audit repro bundle and reports
    whether its divergence reproduces (exit 3 when it does not)."""
    from geomesa_tpu.obs import replay as _replay

    if args.bundle:
        if not args.catalog:
            raise SystemExit("replay --bundle needs -c CATALOG")
        store = _load(args)
        doc = _replay.replay_bundle(store, args.bundle)
        if args.json:
            print(json.dumps(doc, indent=2))
        else:
            print(f"bundle check={doc['check']} type={doc['type']}")
            print(f"recorded divergence: {doc['recorded_detail']}")
            o = doc["original"]
            print(f"original predicate:  "
                  f"{'DIVERGES' if o['diverged'] else 'matches referee'}"
                  + (f" ({o.get('detail')})" if o.get("detail") else ""))
            m = doc.get("minimized")
            if m is not None:
                print(
                    f"minimized predicate: "
                    f"{'DIVERGES' if m['diverged'] else 'matches referee'}"
                    f" [{m['filter']}]")
            print("reproduced" if doc["reproduced"] else "NOT reproduced")
        if not doc["reproduced"]:
            raise SystemExit(3)
        return

    if not args.workload:
        raise SystemExit("replay needs --workload DIR|FILE or --bundle PATH")
    remote = bool(args.url)
    if args.url:
        from geomesa_tpu.store.remote import RemoteDataStore

        store = RemoteDataStore(args.url, timeout_s=args.timeout)
    else:
        if not args.catalog:
            raise SystemExit("replay needs -c CATALOG or --url URL")
        store = _load(args)
    doc = _replay.run(
        store, args.workload,
        tenant=args.tenant, type_name=args.type, source=args.source,
        speed=args.speed, limit=args.limit, remote=remote,
    )
    if args.report:
        _replay.write_report(doc, args.report)
    if doc["events"] == 0:
        # a filter that matched nothing verified nothing — never a pass
        raise SystemExit(
            "error: no captured events matched the filters "
            f"(skipped {doc.get('skipped', 0)} non-replayable) — "
            "check --tenant/--type/--source and the capture path")
    if args.json:
        print(json.dumps(doc, indent=2))
    else:
        skipped = doc.get("skipped", 0)
        print(f"replayed {doc['events']} events ({doc['mode']}): "
              f"parity {'OK' if doc['parity_ok'] else 'FAILED'}, "
              f"{doc['errors']} errors"
              + (f", {skipped} skipped (not forwardable over --url)"
                 if skipped else ""))
        print(f"{'signature':<32s} {'n':>6s} {'rec p50':>9s} "
              f"{'rep p50':>9s} {'rec p95':>9s} {'rep p95':>9s} parity")
        for sig, s in doc["signatures"].items():
            print(f"{sig:<32s} {s['n']:>6d} "
                  f"{s['recorded_ms']['p50']:>9.2f} "
                  f"{s['replayed_ms']['p50']:>9.2f} "
                  f"{s['recorded_ms']['p95']:>9.2f} "
                  f"{s['replayed_ms']['p95']:>9.2f} "
                  f"{'ok' if s['parity'] else 'MISMATCH'}")
        for m in doc.get("row_mismatches", []):
            print(f"  mismatch seq={m['seq']} [{m['signature']}]: "
                  f"recorded {m['recorded_rows']} != replayed "
                  f"{m.get('replayed_rows')} {m.get('error') or ''}")
    if not doc["parity_ok"]:
        raise SystemExit(2)


def main(argv=None):
    p = argparse.ArgumentParser(prog="geomesa-tpu", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    def common(sp, name=True):
        sp.add_argument("-c", "--catalog", required=True, help="catalog directory")
        sp.add_argument("--backend", default="tpu", choices=["tpu", "oracle"])
        if name:
            sp.add_argument("-n", "--name", required=True, help="feature type name")

    sp = sub.add_parser("version")
    sp.set_defaults(fn=cmd_version)

    sp = sub.add_parser("create-schema")
    common(sp)
    sp.add_argument("--spec", required=True)
    sp.set_defaults(fn=cmd_create_schema)

    sp = sub.add_parser("get-type-names")
    common(sp, name=False)
    sp.set_defaults(fn=cmd_get_type_names)

    sp = sub.add_parser("describe-schema")
    common(sp)
    sp.set_defaults(fn=cmd_describe_schema)

    sp = sub.add_parser("update-schema")
    common(sp)
    sp.add_argument("--add", action="append",
                    help="attribute spec to append, e.g. severity:Integer")
    sp.add_argument("--keywords", default=None, help="comma-separated keywords")
    sp.add_argument("--rename-to", default=None)
    sp.set_defaults(fn=cmd_update_schema)

    sp = sub.add_parser("delete-schema")
    common(sp)
    sp.set_defaults(fn=cmd_delete_schema)

    sp = sub.add_parser("ingest")
    common(sp)
    sp.add_argument(
        "--converter", default="delimited",
        help="'delimited' (use --field/--format flags), a converter-config "
        ".json path, a predefined dataset (gdelt, geolife, tdrive, twitter, "
        "nyctaxi, marinecadastre-ais), or a schema-inferring type: avro, "
        "parquet, arrow, shapefile, gpx, gpx-points, osm-nodes, osm-ways",
    )
    sp.add_argument("--format", default="csv", choices=["csv", "tsv"])
    sp.add_argument("--field", action="append", help="attr=expression mapping")
    sp.add_argument("--id-field", default=None)
    sp.add_argument("--header", action="store_true")
    sp.add_argument("--error-mode", default="skip", choices=["skip", "raise"])
    sp.add_argument("files", nargs="+")
    sp.set_defaults(fn=cmd_ingest)

    sp = sub.add_parser("export")
    common(sp)
    sp.add_argument("-q", "--cql", default=None)
    sp.add_argument(
        "--format", default="csv",
        choices=["csv", "json", "arrow", "bin", "avro", "parquet", "orc",
                 "gml", "leaflet", "shp"],
    )
    sp.add_argument("-m", "--max", type=int, default=None)
    sp.add_argument(
        "--start-index", type=int, default=None,
        help="paging offset: rows skipped after sort, before --max",
    )
    sp.add_argument("-a", "--attributes", default=None)
    sp.add_argument("--hints", default=None, help="query hints as JSON")
    sp.add_argument(
        "--srs", default=None,
        help="reproject exported geometries (EPSG code / proj string)",
    )
    sp.add_argument("--bin-track", default=None)
    sp.add_argument("-o", "--output", default=None)
    sp.add_argument(
        "--parallel", type=int, default=None, metavar="N",
        help="write N-worker partitioned output to -o DIR (ExportJob role)",
    )
    sp.set_defaults(fn=cmd_export)

    sp = sub.add_parser("explain")
    common(sp)
    sp.add_argument("-q", "--cql", required=True)
    sp.set_defaults(fn=cmd_explain)

    sp = sub.add_parser(
        "sql", help="run a SQL statement against the catalog (spark-sql role)"
    )
    common(sp, name=False)
    sp.add_argument("-q", "--query", required=True, help="SQL statement")
    sp.add_argument("--format", default="csv", choices=["csv", "json"])
    sp.set_defaults(fn=cmd_sql)

    sp = sub.add_parser("stats-analyze")
    common(sp)
    sp.set_defaults(fn=cmd_stats_analyze)

    sp = sub.add_parser("stats-count")
    common(sp)
    sp.add_argument("-q", "--cql", default=None)
    sp.add_argument("--estimate", action="store_true")
    sp.set_defaults(fn=cmd_stats_count)

    sp = sub.add_parser("stats-top-k")
    common(sp)
    sp.add_argument("-a", "--attribute", required=True)
    sp.add_argument("-k", type=int, default=10)
    sp.set_defaults(fn=cmd_stats_top_k)

    sp = sub.add_parser("stats-histogram")
    common(sp)
    sp.add_argument("-a", "--attribute", required=True)
    sp.add_argument("--bins", type=int, default=10)
    sp.set_defaults(fn=cmd_stats_histogram)

    sp = sub.add_parser(
        "manage-partitions",
        help="list or delete persisted partitions (geomesa-tools role)",
    )
    common(sp)
    sp.add_argument("action", choices=["list", "delete"])
    sp.add_argument("--partition", default=None, help="partition key (delete)")
    sp.set_defaults(fn=cmd_manage_partitions)

    sp = sub.add_parser("serve", help="REST API over the catalog (geomesa-web role)")
    common(sp, name=False)
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=8080)
    sp.add_argument(
        "--auths-header", default=None, metavar="HEADER",
        help="derive visibility auths from this trusted proxy header "
        "(AuthorizationsProvider role); absent header = no auths",
    )
    sp.add_argument(
        "--admit", action="store_true",
        help="enable per-tenant admission control (429 + Retry-After "
        "sheds, SLO-budget-tied refill — docs/serving.md)",
    )
    sp.add_argument(
        "--admit-rate", type=float, default=None, metavar="QPS",
        help="per-tenant admission rate (implies --admit; default "
        "GEOMESA_TPU_ADMIT_RATE or 50)",
    )
    sp.add_argument(
        "--coalesce-ms", type=float, default=None, metavar="MS",
        help="request-coalescing batch window (default "
        "GEOMESA_TPU_COALESCE_MS or 2; 0 disables)",
    )
    sp.add_argument(
        "--journal", default=None, metavar="DIR",
        help="serve this journal root over /api/journal (cross-host "
        "stream transport for hosts with no shared mount)",
    )
    sp.add_argument(
        "--registry", action="store_true",
        help="serve a Confluent-protocol schema registry "
        "(/subjects, /schemas/ids)",
    )
    sp.add_argument(
        "--recover", action="store_true",
        help="open the catalog through the durability plane: take the "
        "WAL lock, load the checkpoint, replay the acked WAL tail, and "
        "journal every mutation while serving (docs/operations.md "
        "§ Durability & recovery)",
    )
    sp.add_argument(
        "--wal", default=None, metavar="DIR",
        help="WAL directory (implies --recover; default GEOMESA_TPU_WAL "
        "or <catalog>/wal)",
    )
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser(
        "wal",
        help="inspect a durability WAL: per-topic records/bytes/seq "
        "ranges, trimmed heads, manifest replay floors, unreplayed tail",
    )
    sp.add_argument("--dir", required=True, metavar="DIR",
                    help="the WAL directory (GEOMESA_TPU_WAL)")
    sp.add_argument("-c", "--catalog", default=None,
                    help="catalog directory: diff the manifest's replay "
                    "floors against the journal (shows the unreplayed "
                    "tail a crash would recover)")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_wal)

    sp = sub.add_parser(
        "compact", help="fold the hot delta tier into the sorted main tier"
    )
    common(sp)
    sp.set_defaults(fn=cmd_compact)

    sp = sub.add_parser(
        "delete-features", help="remove features by id list or CQL filter"
    )
    common(sp)
    g = sp.add_mutually_exclusive_group(required=True)
    g.add_argument("--fids", help="comma-separated feature ids")
    g.add_argument("-q", "--cql", help="delete every feature matching")
    sp.set_defaults(fn=cmd_delete_features)

    sp = sub.add_parser(
        "obs", help="observability surfaces (flight recorder, cost profiles)"
    )
    obs_sub = sp.add_subparsers(dest="obs_command", required=True)

    def obs_common(osp):
        osp.add_argument("--url", required=True,
                         help="server base URL, e.g. http://host:8080")
        osp.add_argument("--limit", type=int, default=32)
        osp.add_argument("--timeout", type=float, default=10.0)
        osp.add_argument("--json", action="store_true",
                         help="raw JSON instead of the table rendering")

    fl = obs_sub.add_parser(
        "flight", help="pull a server's query-audit flight recorder"
    )
    obs_common(fl)
    fl.add_argument("--tenant", default=None,
                    help="only records of this tenant (server-side filter)")
    fl.add_argument("--type", default=None,
                    help="only records of this feature type")
    fl.add_argument("--anomalies", action="store_true",
                    help="only records with anomaly flags")
    fl.set_defaults(fn=cmd_obs_flight)
    co = obs_sub.add_parser(
        "costs", help="pull a server's per-plan-shape observed-cost table"
    )
    obs_common(co)
    co.add_argument("--member", type=int, default=None,
                    help="only one federated member's per-member cost rows")
    co.set_defaults(fn=cmd_obs_costs)
    te = obs_sub.add_parser(
        "tenants", help="pull a server's per-tenant usage accounting"
    )
    obs_common(te)
    te.set_defaults(fn=cmd_obs_tenants)
    au = obs_sub.add_parser(
        "audit", help="pull a server's continuous correctness auditor"
    )
    obs_common(au)
    au.set_defaults(fn=cmd_obs_audit)
    le = obs_sub.add_parser(
        "lens",
        help="pull a server's retained per-plan-signature latency history "
        "(quantiles, exemplars, regression sentinel)",
    )
    obs_common(le)
    le.add_argument("--window", type=float, default=300.0,
                    help="live quantile window in seconds")
    le.add_argument("--type", default=None,
                    help="only series of this feature type")
    le.set_defaults(fn=cmd_obs_lens)
    fu = obs_sub.add_parser(
        "fusion-report",
        help="pull a server's host-roundtrip fusion report (signatures "
        "ranked by host-choreography share)",
    )
    obs_common(fu)
    fu.set_defaults(fn=cmd_obs_fusion)
    lx = obs_sub.add_parser(
        "ledger-export",
        help="pull a server's raw roundtrip-ledger rollup in the stable "
        "reconcile-export schema (tpusync --reconcile input)",
    )
    obs_common(lx)
    lx.add_argument("-o", "--output", default=None,
                    help="write the export here instead of stdout ('-' = "
                    "stdout)")
    lx.set_defaults(fn=cmd_obs_ledger_export)
    sh = obs_sub.add_parser(
        "shards",
        help="pull a server's shard map, live migration states, and "
        "migration counters",
    )
    obs_common(sh)
    sh.set_defaults(fn=cmd_obs_shards)
    sr = obs_sub.add_parser(
        "stream-report",
        help="pull a server's standing-query scale report (subscriptions "
        "ranked by scan-cost share + delivery p99, capacity section, "
        "backlog sentinel)",
    )
    obs_common(sr)
    sr.add_argument("--window", type=float, default=300.0,
                    help="live quantile window in seconds")
    sr.add_argument("--topic", default=None,
                    help="only this topic's subscriptions")
    sr.set_defaults(fn=cmd_obs_stream_report)

    sp = sub.add_parser(
        "replay",
        help="replay a captured workload against a catalog or live server "
        "(recorded-vs-replayed report; exit 2 on row-parity failure)",
    )
    sp.add_argument("-c", "--catalog", default=None, help="catalog directory")
    sp.add_argument("--backend", default="tpu", choices=["tpu", "oracle"])
    sp.add_argument("--url", default=None,
                    help="replay against a live server instead of a catalog")
    sp.add_argument("--workload", default=None,
                    help="capture directory (GEOMESA_TPU_WORKLOAD_DIR) or "
                    "a single capture .jsonl file")
    sp.add_argument("--bundle", default=None,
                    help="an audit repro bundle (GEOMESA_TPU_AUDIT_DIR "
                    "repro-*.json): re-execute its diverging query live + "
                    "referee and report reproduction (exit 3 if not)")
    sp.add_argument("--tenant", default=None, help="replay one tenant only")
    sp.add_argument("--type", default=None, help="replay one type only")
    sp.add_argument("--source", default=None,
                    help="capture tier to re-issue: store | federation "
                    "(default: all — pick one for in-process captures)")
    sp.add_argument("--speed", type=float, default=None,
                    help="open-loop at recorded inter-arrival / SPEED "
                    "(default: closed-loop at max speed)")
    sp.add_argument("--limit", type=int, default=None,
                    help="replay at most N events")
    sp.add_argument("--report", default=None,
                    help="write the full report JSON here (loadable as a "
                    "bench.py --regress baseline)")
    sp.add_argument("--timeout", type=float, default=30.0)
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_replay)

    args = p.parse_args(argv)
    try:
        args.fn(args)
    except (KeyError, ValueError) as e:
        # user-facing errors (unknown schema, bad spec/CQL): message, not traceback
        raise SystemExit(f"error: {e}")


if __name__ == "__main__":
    main()
