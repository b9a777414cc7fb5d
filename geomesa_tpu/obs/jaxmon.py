"""JAX compile/dispatch telemetry (the live counterpart to tpulint J003).

Two hooks:

- :func:`install` registers ``jax.monitoring`` duration listeners so every
  jit compile in the process lands in the telemetry registry as
  ``jax.compile.*`` histograms (trace time, MLIR lowering, backend
  compile). Guarded: a ``GEOMESA_TPU_NO_JAX=1`` process never imports jax
  from here, and a missing/old jax degrades to a no-op.

- :func:`observed` wraps a cached jit step (the ``cached_*_step``
  factories in :mod:`geomesa_tpu.parallel.query`) with a per-call sampler
  that keys calls by ABSTRACT SIGNATURE — the (shape, dtype) tuple jax
  itself caches on. A new signature on an already-warm step is exactly the
  recompile hazard tpulint's J003 flags statically; here it increments
  ``jax.jit.recompiles`` live, with per-step compile/dispatch timing
  histograms and host↔device transfer-byte counters. The sampler itself
  never calls into jax: it reads ``shape``/``dtype``/``nbytes`` attributes
  off whatever arguments arrive and nothing else.

Telemetry is always-on and cheap (~1-2 µs per dispatch, against device
calls that cost milliseconds); SPANS for jit calls are only emitted while
tracing is active (:mod:`geomesa_tpu.obs.trace`).
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from collections import deque

from geomesa_tpu.obs import devmon as _devmon
from geomesa_tpu.obs import ledger as _ledger

__all__ = ["GLOBAL", "registry", "install", "observed", "jit_report",
           "count_h2d", "recompile_census"]

GLOBAL = None  # lazily-created MetricsRegistry (process-wide jax telemetry)
_reg_lock = threading.Lock()
_installed = False


def registry():
    """The process-wide telemetry registry (created on first use)."""
    global GLOBAL
    if GLOBAL is None:
        with _reg_lock:
            if GLOBAL is None:
                from geomesa_tpu.utils.metrics import MetricsRegistry

                GLOBAL = MetricsRegistry()
    return GLOBAL


def _on_duration(name: str, secs: float, **_kw) -> None:
    # e.g. /jax/core/compile/backend_compile_duration → jax.compile.backend_compile
    if "/compile/" not in name:
        return
    tail = name.rsplit("/", 1)[1]
    if tail.endswith("_duration"):
        tail = tail[: -len("_duration")]
    reg = registry()
    reg.histogram(f"jax.compile.{tail}_ms").update(secs * 1000.0)
    reg.counter("jax.compile.events").inc()


def install() -> bool:
    """Register the jax.monitoring listeners (idempotent). Returns True when
    listening; False when jax is gated off or unavailable."""
    global _installed
    if _installed:
        return True
    if os.environ.get("GEOMESA_TPU_NO_JAX"):
        return False
    try:
        import jax.monitoring as jm
    except Exception:  # pragma: no cover — no jax in the process
        return False
    with _reg_lock:
        if _installed:
            return True
        # one-time listener registration, not a dispatch: the lock exists
        # precisely to make this registration idempotent under races
        # tpurace: disable-next-line=R003
        jm.register_event_duration_secs_listener(_on_duration)
        _installed = True
    return True


def _abstract_sig(args: tuple) -> tuple:
    """The jit cache key proxy: (shape, dtype) per array argument, type name
    for everything else (python scalars don't retrigger compiles on value)."""
    out = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            out.append((tuple(shape), str(getattr(a, "dtype", ""))))
        else:
            out.append(type(a).__name__)
    return tuple(out)


def _nbytes(obj) -> int:
    """Total nbytes across an array / tuple-of-arrays result (one level of
    tuple/list nesting — the shapes our steps actually return)."""
    n = getattr(obj, "nbytes", None)
    if n is not None:
        return int(n)
    if isinstance(obj, (tuple, list)):
        return sum(_nbytes(x) for x in obj)
    return 0


def _np_bytes(arrays) -> int:
    """Total nbytes across the NUMPY members of ``arrays`` — THE
    host-side-array detection rule for h2d accounting (one definition;
    device-resident jax arrays never count)."""
    return sum(
        int(a.nbytes)
        for a in arrays
        if type(a).__module__.startswith("numpy") and hasattr(a, "nbytes")
    )


# per-thread record of arrays a call site already accounted via count_h2d:
# the next observed() dispatch on the thread must NOT count them again when
# the same numpy array is passed straight into the step. Weak references —
# never ids — so a recycled id after GC can't alias a fresh array, and the
# pending set never pins a multi-GB staging buffer alive.
_h2d_pending = threading.local()
_H2D_PENDING_CAP = 256

# count_h2d labels whose bytes belong to a SHARED subsystem, not to the
# query that happened to be live when they staged: the buffer pool's
# warm-up staging (ISSUE 7) and the stream scanner's chunk pipeline —
# excluded from the devprof h2d split so a concurrent profiled query's
# attribution stays truthful (pinned in tests/test_stream_matrix.py).
_DEVPROF_EXTERNAL = frozenset({"pool", "stream"})


def _note_pending_h2d(arrays) -> None:
    refs = getattr(_h2d_pending, "refs", None)
    if refs is None:
        refs = _h2d_pending.refs = []
    for a in arrays:
        if type(a).__module__.startswith("numpy") and hasattr(a, "nbytes"):
            try:
                refs.append(weakref.ref(a))
            except TypeError:  # un-weakref-able numpy subclass: skip dedupe
                pass
    if len(refs) > _H2D_PENDING_CAP:
        del refs[:-_H2D_PENDING_CAP]


def _consume_pending_h2d() -> set:
    """The identity set of pre-counted arrays, cleared per dispatch (the
    dedupe window IS one dispatch — staged payloads feed the very next
    step)."""
    refs = getattr(_h2d_pending, "refs", None)
    if not refs:
        return set()
    out = {id(a) for a in (r() for r in refs) if a is not None}
    refs.clear()
    return out


def count_h2d(*arrays, label: str | None = None) -> int:
    """Account host→device staging for numpy arrays about to be
    ``jnp.asarray``'d / ``device_put`` / passed to a dispatch (transfers
    the step wrapper cannot see when call sites pre-convert). Non-numpy
    args are skipped — device-resident columns must not be recounted per
    dispatch. Arrays counted here are remembered (weakly, per thread) so
    a call site that passes the SAME numpy array straight into the next
    ``observed()`` dispatch is not double-counted. Returns bytes counted.

    ``label``: attribution bucket, additionally counted under
    ``jax.transfer.h2d_bytes.<label>``. Bytes staged by a SHARED subsystem
    — a buffer-pool warm-up/miss (``label="pool"``) or the stream
    scanner's chunk pipeline (``label="stream"``) — belong to that
    subsystem, not to the query that happened to be live: they are
    excluded from the live devprof profile, so per-query h2d splits stay
    truthful. Unlabeled (query-side) staging IS attributed to the
    profiled query."""
    total = _np_bytes(arrays)
    if total:
        reg = registry()
        reg.counter("jax.transfer.h2d_bytes").inc(total)
        if label:
            reg.counter(f"jax.transfer.h2d_bytes.{label}").inc(total)
        _note_pending_h2d(arrays)
        if label not in _DEVPROF_EXTERNAL and _devmon.PROFILING:
            prof = _devmon.current_profile()
            if prof is not None:
                prof.note_h2d(total)
    return total


# -- recompile census → flight recorder (A_RECOMPILE) -------------------------
# The live J003 dashboard already counts recompiles; the census turns a
# BURST of them into one operator signal: >= GEOMESA_TPU_RECOMPILE_STORM
# recompiles inside a GEOMESA_TPU_RECOMPILE_WINDOW_S window raises ONE
# rate-limited A_RECOMPILE flight anomaly (the recorder's dump throttle
# bounds file output). Recompiles are rare by design (the zero-recompile
# census pins in tests/test_costmodel.py), so this path is cold.
_RECOMPILE_WINDOW_S = float(
    os.environ.get("GEOMESA_TPU_RECOMPILE_WINDOW_S", "60"))
_RECOMPILE_STORM = int(os.environ.get("GEOMESA_TPU_RECOMPILE_STORM", "3"))
_census_lock = threading.Lock()  # leaf: census window + storm clock
_census_times: deque = deque(maxlen=256)  # (ts, step) inside the window
_census_last_storm = -float("inf")
_census_storms = 0


def _note_recompile(step: str) -> None:
    now = time.time()
    burst = 0
    with _census_lock:
        global _census_last_storm, _census_storms
        _census_times.append((now, step))
        horizon = now - _RECOMPILE_WINDOW_S
        while _census_times and _census_times[0][0] < horizon:
            _census_times.popleft()
        n = len(_census_times)
        if (n >= _RECOMPILE_STORM
                and now - _census_last_storm >= _RECOMPILE_WINDOW_S):
            _census_last_storm = now  # one anomaly per window
            _census_storms += 1
            burst = n
    if burst:
        from geomesa_tpu.obs import flight as _flight

        _flight.record(
            "jit.recompile", "", source="jaxmon",
            plan=(f"{burst} recompiles in {_RECOMPILE_WINDOW_S:.0f}s "
                  f"window (latest step: {step})"),
            anomalies=(_flight.A_RECOMPILE,),
        )


def recompile_census() -> dict:
    """The census state (``/api/metrics`` + tests): recompiles inside the
    current window, the storm threshold, and storms raised so far."""
    with _census_lock:
        return {
            "window_s": _RECOMPILE_WINDOW_S,
            "threshold": _RECOMPILE_STORM,
            "in_window": len(_census_times),
            "storms": _census_storms,
        }


def _census_reset() -> None:
    """Test hook: clear the census window and storm clock."""
    with _census_lock:
        global _census_last_storm, _census_storms
        _census_times.clear()
        _census_last_storm = -float("inf")
        _census_storms = 0


def _block_ready(obj) -> None:
    """Wait for every array in ``obj`` (one level of tuple/list nesting —
    the shapes our steps return) to finish on device."""
    if hasattr(obj, "block_until_ready"):
        obj.block_until_ready()
    elif isinstance(obj, (tuple, list)):
        for x in obj:
            _block_ready(x)


def _materialize(obj) -> None:
    """Force the device→host copy the caller is about to pay (the d2h
    half of the attribution bracket)."""
    import numpy as np

    if hasattr(obj, "block_until_ready"):
        np.asarray(obj)
    elif isinstance(obj, (tuple, list)):
        for x in obj:
            _materialize(x)


def _profiled_call(fn, args, kwargs, sp):
    """The sampled devprof bracket around one dispatch: timed host→device
    staging of numpy arguments (converted here so the step receives
    device arrays — identical semantics, but the transfer is visible),
    the dispatch call itself, a ``block_until_ready`` device wait, and a
    timed device→host materialization of the results. Returns
    ``((h2d_ms, call_ms, device_ms, d2h_ms), out)``. Only ever runs
    inside a live :func:`geomesa_tpu.obs.devmon.profiled` context."""
    import jax

    pc = time.perf_counter
    cm = sp if sp is not None else _NullCtx()
    with cm:
        t0 = pc()
        staged = []
        converted = []
        for a in args:
            if type(a).__module__.startswith("numpy") and hasattr(a, "nbytes"):
                d = jax.device_put(a)
                staged.append(d)
                converted.append(d)
            else:
                staged.append(a)
        if converted:
            _block_ready(converted)
        t1 = pc()
        with _DISPATCH_GATE:
            out = fn(*staged, **kwargs)
        t2 = pc()
        _block_ready(out)
        t3 = pc()
        _materialize(out)
        t4 = pc()
    return (
        ((t1 - t0) * 1000.0, (t2 - t1) * 1000.0,
         (t3 - t2) * 1000.0, (t4 - t3) * 1000.0),
        out,
    )


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


# One process-wide enqueue gate for sharded dispatches. JAX requires
# multi-device computations to be ENQUEUED in the same order on every
# device; two threads racing execute_sharded can invert the per-device
# queue order and deadlock the collective rendezvous (observed as reader
# threads parked in ``array._value`` while a third pjit never finishes).
# Enqueue is async and returns in microseconds — results are awaited
# OUTSIDE the gate — so concurrent queries still overlap on device; the
# gate only pins the cross-device launch order. RLock, not Lock: a step
# that re-enters Python (host fallback inside a wrapped step) must not
# self-deadlock.
_DISPATCH_GATE = threading.RLock()


def _name_module(fn, name: str):
    """Make the jitted ``fn`` lower to the XLA module ``jit_<name>``.

    jax names a module after the Python function it traces, and every
    step factory calls that function ``step``, so without this each
    program is ``jit_step`` in a device trace's ``XLA Modules`` line.
    Renaming the traced function before its first trace changes the
    module's name only: the HLO, and so the compiled program, are the
    same. Anything but a jitted function is returned untouched."""
    inner = getattr(fn, "__wrapped__", None)
    if inner is not None and hasattr(fn, "lower"):
        inner.__name__ = name
    return fn


def observed(name: str, fn):
    """Wrap one cached jit step with the signature-keyed sampler.

    Applied INSIDE the ``lru_cache`` factories, so each distinct compiled
    step owns one wrapper and one signature set for the life of the
    cache. Metric handles are resolved ONCE here (names are fixed per
    wrapper) so the per-dispatch cost is increments, not name lookups.
    The step lowers to the XLA module ``jit_<name>`` (:func:`_name_module`),
    so its device time carries the same name as its census.
    """
    from geomesa_tpu.obs import trace as _trace

    _name_module(fn, name)

    sigs: set = set()
    lock = threading.Lock()
    reg = registry()
    calls = reg.counter(f"jax.jit.{name}.calls")
    compiles = reg.counter(f"jax.jit.{name}.compiles")
    compile_ms = reg.histogram(f"jax.jit.{name}.compile_dispatch_ms")
    dispatch_ms = reg.histogram(f"jax.jit.{name}.dispatch_ms")
    recompiles = reg.counter(f"jax.jit.{name}.recompiles")
    recompiles_all = reg.counter("jax.jit.recompiles")
    h2d_bytes = reg.counter("jax.transfer.h2d_bytes")
    d2h_bytes = reg.counter("jax.transfer.d2h_bytes")

    def wrapper(*args, **kwargs):
        key = _abstract_sig(args)
        with lock:
            is_new = key not in sigs
            if is_new:
                sigs.add(key)
            n_sigs = len(sigs)
        sp = _trace.span("jit", step=name) if _trace.active() else None
        # devprof: ONE module-global bool check on the off path (<2%
        # bound); the ContextVar read happens only while some profiled()
        # context is live anywhere in the process
        prof = _devmon.current_profile() if _devmon.PROFILING else None
        prof_detail = None
        t0 = time.perf_counter()
        try:
            if prof is not None:
                prof_detail, out = _profiled_call(fn, args, kwargs, sp)
            elif sp is not None:
                with sp:
                    with _DISPATCH_GATE:
                        out = fn(*args, **kwargs)
            else:
                with _DISPATCH_GATE:
                    out = fn(*args, **kwargs)
        except BaseException:
            # the signature only counts once the step SUCCEEDS: a device
            # error here (circuit-breaker failover) must leave the retry
            # classified as the compile it really is, not a warm dispatch
            if is_new:
                with lock:
                    sigs.discard(key)
            raise
        t1 = time.perf_counter()
        dt_ms = (t1 - t0) * 1000.0
        calls.inc()
        # transfer denominator: numpy args are about to cross host→device
        # (call sites that pre-convert account theirs via count_h2d —
        # dedupe by array identity so the SAME array arriving here after a
        # count_h2d on this thread is never counted twice per dispatch);
        # result bytes cross back when the caller materializes them
        pre = _consume_pending_h2d()
        h2d = sum(
            int(a.nbytes)
            for a in args
            if type(a).__module__.startswith("numpy")
            and hasattr(a, "nbytes") and id(a) not in pre
        )
        d2h = _nbytes(out)
        if h2d:
            h2d_bytes.inc(h2d)
        if d2h:
            d2h_bytes.inc(d2h)
        # roundtrip ledger (obs.ledger): one ContextVar read when no query
        # context is open; the on path charges this dispatch's span +
        # transfer bytes to the live query's ledger
        _ledger.note_dispatch(t0, t1, compiled=is_new,
                              h2d_bytes=h2d, d2h_bytes=d2h)
        if is_new:
            compiles.inc()
            compile_ms.update(dt_ms)
            if n_sigs > 1:
                # a warm step met a fresh abstract signature: the live J003
                recompiles_all.inc()
                recompiles.inc()
                _note_recompile(name)
        else:
            dispatch_ms.update(dt_ms)
        if sp is not None:
            sp.set(compile=is_new, ms=round(dt_ms, 3),
                   h2d_bytes=h2d, d2h_bytes=d2h)
        if prof is not None and prof_detail is not None:
            h2d_ms, call_ms, device_ms, d2h_ms = prof_detail
            prof.add(
                name,
                compile_ms=call_ms if is_new else 0.0,
                dispatch_ms=0.0 if is_new else call_ms,
                device_ms=device_ms, h2d_ms=h2d_ms, d2h_ms=d2h_ms,
                h2d_bytes=h2d, d2h_bytes=d2h,
            )
            if sp is not None:
                sp.set(device_ms=round(device_ms, 3),
                       h2d_ms=round(h2d_ms, 3), d2h_ms=round(d2h_ms, 3))
        return out

    wrapper.__name__ = f"observed_{name}"
    wrapper.__wrapped__ = fn
    return wrapper


def jit_report() -> dict:
    """Per-step jit census: calls, distinct-signature compiles, recompiles,
    and dispatch timing — the live J003 dashboard."""
    if GLOBAL is None:
        return {}
    snap = GLOBAL.snapshot()
    steps: dict[str, dict] = {}
    for k, v in snap.items():
        if not k.startswith("jax.jit."):
            continue
        rest = k[len("jax.jit."):]
        if "." not in rest:
            continue  # jax.jit.recompiles global counter
        step, metric = rest.rsplit(".", 1)
        if metric in ("calls", "compiles", "recompiles"):
            steps.setdefault(step, {})[metric] = v.get("count", 0)
        elif metric in ("dispatch_ms", "compile_dispatch_ms"):
            steps.setdefault(step, {})[metric] = {
                kk: vv for kk, vv in v.items() if kk != "type"
            }
    out = {"steps": steps}
    if "jax.jit.recompiles" in snap:
        out["recompiles"] = snap["jax.jit.recompiles"]["count"]
    for k in ("jax.transfer.h2d_bytes", "jax.transfer.d2h_bytes"):
        if k in snap:
            out[k.rsplit(".", 1)[1]] = snap[k]["count"]
    return out
