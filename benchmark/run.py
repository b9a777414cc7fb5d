"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``configs/<config>.json``, whose ``generator`` names
``datagen/<generator>.py``), its traffic mix (``traffic/<mix>.json``,
whose ``call`` names ``calls/<call>.py``) and, with ``--trace 1``, one
reader per per-layer metric (``metrics/<metric>.py``).

One process: generate the data from the seed, build the store, warm up
(the window's own requests, each CQL respelled, so every program the
window runs is compiled and the plan cache still misses), then a closed
loop of one client for ``--seconds`` seconds, each request timed from
the call until the client holds the answer on the host. After the window: the peak HBM reading, the store
freed, and every answer of the window compared with the plain reference.
The last stdout line is one JSON object; the numbers compared, each with
its limit, are the last lines of stderr and the result's last key.

The store is built on a mesh of the cell's ``chips`` devices. No TPU,
fewer chips than the cell asks for, or an index that set-up leaves on
another number of devices: exit 3 and no result. ``--rehearse`` runs the
configuration's tiny ``rehearse`` size on whatever JAX finds (the CPU
here), on the cell's chips or as many devices as there are, and names
that device.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from contextlib import ExitStack  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# the compile cache at a fixed path inside the checkout, whatever the
# machine sets: JAX and the program's enable_compile_cache both read it
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / ".traces"
WARM_MIN_REQUESTS = 16
WARM_MAX_S = 180.0  # a warm-up that keeps compiling stops here


class NoChip(RuntimeError):
    pass


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    from benchmark.traffic import load_mix

    return bench, cell, cfg, load_mix(cell["traffic"])


def check_device(chips: int, rehearse: bool):
    import jax

    devices = jax.devices()
    if not rehearse:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devices[0].platform!r}")
        if len(devices) < chips:
            raise NoChip(f"{chips} chips asked, {len(devices)} found")
    return devices[0], min(chips, len(devices))


def _metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"benchmark_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def warm_up(ds, type_name, call, traffic, clock, seconds, share, deck) -> int:
    """Replay the window's requests, each CQL spelled anew, until those
    that compiled nothing add up to ``share`` of the window's seconds (at
    least WARM_MIN_REQUESTS requests), in whole decks; returns the
    requests made. Whole decks keep any periodic choice the program
    makes per query (its cost model probes the losing select route every
    16th query) on the same requests in the warm-up and in the window."""
    t_begin = time.perf_counter()
    covered = 0.0
    k = 0
    while k < WARM_MIN_REQUESTS or covered < share * seconds or k % deck:
        before = clock.backend_compiles
        t0 = time.perf_counter()
        call.send(ds, type_name, [f"({q})" for _, _, q in traffic.request(k)])
        if clock.backend_compiles == before:
            covered += time.perf_counter() - t0
        k += 1
        if time.perf_counter() - t_begin > WARM_MAX_S:
            break
    return k


def window(ds, type_name, call, traffic, seconds, roots=None):
    """The closed loop. Returns (requests, answers kept, latencies s,
    elapsed s, failures, perf_counter_ns starts). With ``roots`` (a list)
    each request runs under a profiler annotation and a span collector,
    whose root is appended to ``roots``."""
    import jax

    requests, kept, lat, starts = [], [], [], []
    failed = 0
    t_begin = time.perf_counter()
    deadline = t_begin + seconds
    t_end = t_begin
    while t_end < deadline:
        req = traffic.request(len(requests))
        cqls = [q for _, _, q in req]
        with ExitStack() as traced:
            if roots is not None:
                from geomesa_tpu import obs

                traced.enter_context(jax.profiler.TraceAnnotation("bench.request"))
                starts.append(time.perf_counter_ns())
                roots.append(traced.enter_context(obs.collect("bench.request")))
            t0 = time.perf_counter()
            try:
                ans = call.send(ds, type_name, cqls)
            except Exception as e:  # noqa: BLE001 — counted, reported
                ans, err = None, e
            t1 = time.perf_counter()
        t_end = t1
        requests.append(req)
        lat.append(t1 - t0)
        if ans is None:
            failed += 1
            print(f"request {len(requests) - 1} failed: {err!r}", file=sys.stderr)
            kept.append(None)
        else:
            kept.append(call.keep(ans))
    return requests, kept, lat, t_end - t_begin, failed, starts


def judge(call, referee, requests, kept, fid_base: int = 0) -> dict:
    totals = dict.fromkeys(call.LIMITS, 0)
    for req, got in zip(requests, kept):
        want = call.expected(referee, req)
        if got is None:
            got = []  # a request that failed answered nothing
        for k, v in call.judge(got, want, fid_base).items():
            totals[k] += v
    return totals


def route_counts(type_name: str) -> dict:
    """The program's cost table: queries served so far, and their p50 ms,
    by select route (``sel:twopass`` / ``sel:planned``), for the log."""
    from geomesa_tpu.obs import devmon

    return {r["signature"]: (r["count"], r["wall_ms_p50"])
            for r in devmon.costs().snapshot()["entries"]
            if r["type"] == type_name and r["signature"].startswith("sel:")}


def percentile(values, q: int) -> float:
    """The q-th percentile (Python's exclusive quantiles, n=100)."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100)[q - 1]


def run(args) -> int:
    bench, cell, cfg, mix = load_cell(args.workload)
    if args.rehearse:
        cfg = {**cfg, **cfg["rehearse"]}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # libtpu would otherwise log under the fixed /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        dev, chips = check_device(int(cell["chips"]), args.rehearse)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3

    import geomesa_tpu  # noqa: F401 — enables x64 before any array work
    import numpy as np
    from geomesa_tpu.utils.compile_cache import enable_compile_cache

    from benchmark import reference, store, traffic

    enable_compile_cache()
    clock = store.CompileClock()
    call = importlib.import_module(f"benchmark.calls.{mix['call']}")
    gen = importlib.import_module(f"benchmark.datagen.{cfg['generator']}")
    type_name = cfg["type_name"]

    phases = {"start_s": time.perf_counter() - T_START}
    data = gen.generate(cfg, args.seed)
    phases["data_s"] = time.perf_counter() - T_START
    try:
        ds = store.build_store(cfg, data, chips)
    except store.NotResident as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    phases["build_s"] = time.perf_counter() - T_START
    reqs = traffic.Traffic(mix, cfg, data, args.seed, traffic.STREAM_WINDOW)
    warm = warm_up(ds, type_name, call, reqs, clock, float(args.seconds),
                   float(mix.get("warm_window_share", 0.0)), int(mix["deck"]))
    setup_s = time.perf_counter() - T_START
    compiles_before = clock.backend_compiles
    routes_before = route_counts(type_name)
    roots = [] if args.trace else None
    if args.trace:
        import jax

        from geomesa_tpu.obs import jaxmon
        from geomesa_tpu.obs import ledger as rtledger

        shutil.rmtree(TRACE_DIR / args.workload, ignore_errors=True)
        ledger0 = rtledger.table().export()
        calls0 = jaxmon.jit_report().get("steps", {})
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR / args.workload),
                                 profiler_options=opts)
    try:
        requests, kept, lat, elapsed, failed, starts = window(
            ds, type_name, call, reqs, float(args.seconds), roots)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    window_compiles = clock.backend_compiles - compiles_before
    routes = {k: (n - routes_before.get(k, (0, 0))[0], p50)
              for k, (n, p50) in route_counts(type_name).items()}
    peaks = store.peak_hbm(store.mesh_devices(ds))
    memory_peak = max((b for b in peaks if b is not None), default=None)
    z3_devices = store.residency(ds, type_name)
    health = store.device_health(ds)
    n_cqls = sum(len(r) for r in requests)

    if args.trace:
        ctx = SimpleNamespace(
            roots=roots, requests=len(requests), queries=n_cqls,
            ledger0=ledger0, ledger1=rtledger.table().export(),
            calls0=calls0, calls1=jaxmon.jit_report().get("steps", {}),
            type_name=type_name, device_kind=dev.device_kind,
            resident=store.resident_columns(ds, type_name),
        )
    ds.close()
    del ds

    # the check: every answer of the window against the plain reference
    t_ref = time.perf_counter()
    referee = reference.Referee(data)
    picked = range(len(requests))
    if len(requests) > mix.get("check_requests", len(requests)):
        rng = np.random.default_rng([args.seed, traffic.STREAM_SAMPLE])
        picked = np.sort(rng.choice(len(requests), mix["check_requests"],
                                    replace=False))
    checks = judge(call, referee, [requests[i] for i in picked],
                   [kept[i] for i in picked], data.fid_base)
    checks["requests_failed"] = failed
    checks.update(health)
    limits = {**call.LIMITS, "requests_failed": 0,
              **dict.fromkeys(health, 0)}
    ref_s = time.perf_counter() - t_ref
    correct = all(checks[k] <= limits[k] for k in limits)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(requests), "failed": failed}
    if args.trace:
        from benchmark import trace_reduce

        path = trace_reduce.find_xplane(TRACE_DIR / args.workload)
        red = trace_reduce.reduce(path, starts, roots) if path else None
        ctx.trace = red
        ctx.peaks = _peaks(dev.device_kind) if red is not None else None
        metrics = {}
        for m in bench["per_layer"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            v = _metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = red["breakdown"]
    else:
        values = {
            "queries_per_s": n_cqls / elapsed,
            "latency_p50_ms": statistics.median(lat) * 1000.0,
            "latency_p95_ms": percentile(lat, 95) * 1000.0,
            "setup_s": setup_s,
        }
        metrics = {}
        for m in bench["end_to_end"]:
            if args.workload in m.get("workloads", [args.workload]):
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]} for k in limits}
    info = {"workload": args.workload, "seed": args.seed, "requests": len(requests),
            "checked_requests": len(picked),
            "cqls": n_cqls, "window_s": elapsed, "warm_requests": warm,
            "window_compiles": window_compiles, "reference_s": ref_s,
            "setup_s": setup_s, "phases_at_s": phases,
            "memory_peak_bytes_by_device": peaks, "z3_devices": z3_devices,
            "window_routes": routes,
            "compile": clock.report()}
    print(json.dumps(info), file=sys.stderr)
    for k in limits:
        print(f"check {k} = {checks[k]} (limit {limits[k]})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever device JAX finds")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
