"""Hierarchical trace spans with ContextVar propagation (the obs core).

The per-query timeline the reference never had: every stage of
``QueryPlanner.runQuery`` (plan → range decomposition → device dispatch →
refine → reduce → serialize) opens a :class:`Span`; spans nest through a
``contextvars.ContextVar``, so propagation is correct across the threaded
web server's request threads and the watchdog's scan worker threads
(``utils.timeouts.run_with_timeout`` copies the context into its worker)
without any explicit plumbing.

Zero-overhead contract: with tracing disabled, :func:`span` returns a
shared no-op context manager after one module-global check and one
ContextVar read — no allocation, no clock read, no jax. The bound is
asserted by ``tests/test_obs.py``.

Profiler bridge: every recorded span also opens a
``jax.profiler.TraceAnnotation`` named ``geomesa.<span name>`` for its
lifetime, so a ``jax.profiler`` capture of the live system shows the
program's spans as host events on the same clock as the device ops. jax
is resolved lazily by the first recorded span, and never where
``GEOMESA_TPU_NO_JAX=1`` is set: spans still record there, unbridged.
The prefix keeps bridged events apart from annotations a caller opens
itself under the span's bare name.

Enable globally with :func:`enable` (or any non-empty
``GEOMESA_TPU_TRACE`` in the environment, which child processes inherit),
or per-call-tree with :func:`collect` (what ``DataStore.explain(...,
analyze=True)`` uses). Completed root spans land in a bounded in-memory
buffer; exporters (:mod:`geomesa_tpu.obs.export`) turn them into
Chrome/Perfetto trace JSON.
"""

from __future__ import annotations

import base64
import itertools
import json
import os
import threading
import time
import zlib
from collections import deque
from contextlib import contextmanager
from contextvars import ContextVar

__all__ = [
    "Span", "StageTimeline", "span", "collect", "current", "annotate",
    "enable", "disable", "enabled", "event", "recent", "drain", "NOOP",
    "TRACE_HEADER", "TRACE_RETURN_HEADER", "TraceContext", "inject",
    "extract", "propagated", "remote_owned", "serialize_subtree",
    "deserialize_subtree", "graft_serialized", "on_root_complete",
    "remove_root_listener", "unsampled_join",
]

# -- cross-process trace context (docs/observability.md § Distributed
# tracing). The request header carries ``trace_id;parent_span_id;flags``
# (flags bit 0 = sampled, W3C-traceparent style); the response header
# carries back a compact serialized span subtree the client grafts under
# its RPC span, so one federated query reads as ONE stitched tree.
TRACE_HEADER = "X-Geomesa-Trace"
TRACE_RETURN_HEADER = "X-Geomesa-Trace-Return"

_enabled = False  # module-global fast flag (the one check on the no-op path)
_forced: ContextVar[bool] = ContextVar("geomesa_obs_forced", default=False)
_current: ContextVar["Span | None"] = ContextVar("geomesa_obs_span", default=None)
# True inside a server-side `propagated` tree: the REMOTE caller owns this
# trace (the flight recorder must not park anomaly dumps on it — the
# local propagated root completing is not the stitched tree completing)
_remote_owned: ContextVar[bool] = ContextVar("geomesa_obs_remote", default=False)
# True inside a request joined from an UNSAMPLED incoming context:
# downstream inject() must carry flags=0 so the next hop does not
# force-record either — the flag is honored END TO END, not just here
_unsampled: ContextVar[bool] = ContextVar("geomesa_obs_unsampled", default=False)

_buffer_lock = threading.Lock()
_MAX_TRACES = 512  # completed root spans retained (ring buffer)
_traces: deque = deque(maxlen=_MAX_TRACES)

# span/trace ids: a per-process random salt + cheap counter — unique within
# and across processes without paying uuid4 per span
_salt = os.urandom(4).hex()
_ids = itertools.count(1)

# completed-root listeners: fn(root_span), registered by the flight
# recorder so anomaly dumps fire only once the whole tree is closed
_root_listeners: list = []

# profiler bridge (module docstring): None until the first recorded span
# resolves it, then jax.profiler.TraceAnnotation, or False where jax is
# gated off or missing
BRIDGE_PREFIX = "geomesa."
_annotation = None


def _resolve_annotation():
    global _annotation
    ann = False
    if not os.environ.get("GEOMESA_TPU_NO_JAX"):
        try:
            from jax.profiler import TraceAnnotation as ann
        except ImportError:  # no jax: spans stay unbridged
            pass
    _annotation = ann
    return ann


class Span:
    """One timed stage. Context manager; children attach automatically via
    the ContextVar, so concurrent requests build disjoint trees."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "attrs", "children",
        "events", "t0_ns", "t1_ns", "thread_id", "_token", "_prof",
    )

    def __init__(self, name: str, attrs: dict, parent: "Span | None"):
        self.name = name
        self.attrs = attrs
        self.children: list[Span] = []
        # point-in-time markers inside this span's window — (name, t_ns,
        # attrs) — the federation layer's member-error/degradation record
        self.events: list[tuple] = []
        sid = next(_ids)
        self.span_id = f"{_salt}-{sid:x}"
        if parent is None:
            self.trace_id = f"{_salt}-t{sid:x}"
            self.parent_id = ""
        else:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        self.t0_ns = 0
        self.t1_ns = 0
        self.thread_id = threading.get_ident()
        self._token = None
        self._prof = None

    # -- timing ---------------------------------------------------------------
    @property
    def duration_ms(self) -> float:
        end = self.t1_ns if self.t1_ns else time.perf_counter_ns()
        return (end - self.t0_ns) / 1e6

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs) -> "Span":
        """Record a point-in-time marker on this span (list.append is
        atomic under the GIL; exporters snapshot via list())."""
        self.events.append((name, time.perf_counter_ns(), attrs))
        return self

    def __enter__(self) -> "Span":
        ann = _annotation if _annotation is not None else _resolve_annotation()
        if ann:
            # name only: the attributes stay on the Span
            self._prof = ann(BRIDGE_PREFIX + self.name)
            self._prof.__enter__()
        self._token = _current.set(self)
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1_ns = time.perf_counter_ns()
        if self._prof is not None:
            self._prof.__exit__(exc_type, exc, tb)
            self._prof = None
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        parent = None
        if self._token is not None:
            prev = self._token.old_value  # Token.MISSING when var was unset
            _current.reset(self._token)
            self._token = None
            if isinstance(prev, Span):
                parent = prev
        if parent is not None:
            # list.append is atomic under the GIL; an abandoned (timed-out)
            # scan worker may attach late — exporters snapshot via list()
            parent.children.append(self)
        else:
            with _buffer_lock:
                _traces.append(self)
            # completed-root listeners (the flight recorder's anomaly-dump
            # trigger): called OUTSIDE the buffer lock, errors swallowed —
            # a broken listener must never fail the traced call itself
            for fn in list(_root_listeners):
                try:
                    fn(self)
                except Exception:  # noqa: BLE001 — observer, not participant
                    pass

    # -- introspection --------------------------------------------------------
    def walk(self):
        """This span and every descendant, depth-first."""
        yield self
        for c in list(self.children):
            yield from c.walk()

    def find(self, name: str) -> "list[Span]":
        return [s for s in self.walk() if s.name == name]

    def __repr__(self) -> str:  # pragma: no cover — debug aid
        return (
            f"Span({self.name!r}, {self.duration_ms:.3f} ms, "
            f"children={len(self.children)})"
        )


class _NoopSpan:
    """The disabled path: one shared instance, every method a no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **attrs):
        return self

    def event(self, name, **attrs):
        return self

    # mimic the Span read surface so call sites never branch on type
    name = ""
    trace_id = ""
    span_id = ""
    parent_id = ""
    attrs: dict = {}
    children: list = []
    events: list = []
    duration_ms = 0.0

    def walk(self):
        return iter(())

    def find(self, name):
        return []


NOOP = _NoopSpan()


def active() -> bool:
    """True when spans are being recorded on THIS context (global enable or
    an enclosing :func:`collect`)."""
    return _enabled or _forced.get()


def enabled() -> bool:
    return _enabled


def enable(jax_telemetry: bool = True) -> None:
    """Turn tracing on process-wide. ``jax_telemetry`` also installs the
    jax.monitoring compile listeners — guarded so a ``GEOMESA_TPU_NO_JAX=1``
    process never imports jax from here."""
    global _enabled
    _enabled = True
    if jax_telemetry:
        from geomesa_tpu.obs import jaxmon

        jaxmon.install()


def disable() -> None:
    global _enabled
    _enabled = False


def span(name: str, **attrs) -> "Span | _NoopSpan":
    """Open a child span of the current context (a root when none).

    Usage: ``with obs.span("plan", index="z3"): ...`` — returns the shared
    no-op singleton when tracing is off.
    """
    if not _enabled and not _forced.get():
        return NOOP
    return Span(name, attrs, _current.get())


def current() -> "Span | None":
    """The innermost live span on this context, or None."""
    return _current.get()


def annotate(**attrs) -> None:
    """Attach attributes to the innermost live span (no-op when untraced)."""
    sp = _current.get()
    if sp is not None:
        sp.attrs.update(attrs)


def event(name: str, **attrs) -> None:
    """Record a point-in-time marker on the innermost live span (no-op
    when untraced) — e.g. a federation member error inside a query span."""
    sp = _current.get()
    if sp is not None:
        sp.event(name, **attrs)


@contextmanager
def collect(name: str = "trace", **attrs):
    """Force-trace one call tree regardless of the global flag and yield its
    root span (inspect ``root.children`` after the block). This is the
    ``EXPLAIN ANALYZE`` mechanism: per-query opt-in with zero ambient cost."""
    tok = _forced.set(True)
    root = Span(name, attrs, _current.get())
    try:
        with root:
            yield root
    finally:
        _forced.reset(tok)


def on_root_complete(fn) -> None:
    """Register ``fn(root_span)`` to run whenever a root span completes
    (after it lands in the buffer; called outside every obs lock)."""
    _root_listeners.append(fn)


def remove_root_listener(fn) -> None:
    try:
        _root_listeners.remove(fn)
    except ValueError:
        pass


# -- cross-process propagation (the federation trace contract) ---------------

class TraceContext:
    """Parsed ``X-Geomesa-Trace`` header: the caller's trace identity plus
    the sampled flag a remote member must honor."""

    __slots__ = ("trace_id", "parent_span_id", "sampled")

    def __init__(self, trace_id: str, parent_span_id: str, sampled: bool):
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.sampled = sampled

    def header_value(self) -> str:
        return f"{self.trace_id};{self.parent_span_id};{int(self.sampled)}"

    def __repr__(self) -> str:  # pragma: no cover — debug aid
        return (f"TraceContext({self.trace_id!r}, {self.parent_span_id!r}, "
                f"sampled={self.sampled})")


def inject() -> str | None:
    """Header value for the innermost live span (None when untraced) —
    what the HTTP choke point stamps on every outbound RPC. A locally
    originated trace is sampled (we ARE recording it); a tree joined from
    an unsampled upstream context stays unsampled downstream."""
    sp = _current.get()
    if sp is None:
        return None
    flags = 0 if _unsampled.get() else 1
    return f"{sp.trace_id};{sp.span_id};{flags}"


@contextmanager
def unsampled_join():
    """Mark this call tree as joined from an UNSAMPLED incoming context:
    local spans may still record (ids join the caller's trace), but
    outbound :func:`inject` carries flags=0 so downstream members are not
    force-recorded — honoring the caller's sampling decision end to end
    (the web layer wraps unsampled-context requests in this)."""
    tok = _unsampled.set(True)
    try:
        yield
    finally:
        _unsampled.reset(tok)


def extract(header: str | None) -> TraceContext | None:
    """Parse an incoming ``X-Geomesa-Trace`` header. Malformed values
    yield None (propagation is best-effort, never a request error)."""
    if not header:
        return None
    parts = header.split(";")
    if len(parts) != 3 or not parts[0] or not parts[1]:
        return None
    trace_id, parent_id, flags = parts
    if any(len(p) > 128 for p in parts):
        return None  # defensive: a hostile header must not bloat every span
    return TraceContext(trace_id, parent_id, flags.strip() == "1")


@contextmanager
def propagated(name: str, ctx: TraceContext, **attrs):
    """Server-side trace join: force-record one call tree as a child of
    the remote caller's span (the ``collect`` mechanism with the caller's
    ids), honoring the sampled flag — this is how a remote member's spans
    end up inside the federated caller's stitched tree."""
    tok = _forced.set(True)
    rtok = _remote_owned.set(True)
    root = Span(name, attrs, _current.get())
    root.trace_id = ctx.trace_id
    root.parent_id = ctx.parent_span_id
    try:
        with root:
            yield root
    finally:
        _remote_owned.reset(rtok)
        _forced.reset(tok)


def remote_owned() -> bool:
    """True when this context's trace is owned by a remote caller (we are
    inside a server-side ``propagated`` tree)."""
    return _remote_owned.get()


def _prim(v):
    if isinstance(v, (int, float, bool, str, type(None))):
        return v
    s = str(v)
    return s if len(s) <= 200 else s[:197] + "..."


def _span_doc(s: Span, base_ns: int, depth: int) -> dict:
    d = {
        "n": s.name,
        "i": s.span_id,
        "th": s.thread_id,
        "o": s.t0_ns - base_ns,
        "d": max((s.t1_ns or s.t0_ns) - s.t0_ns, 0),
        "a": {k: _prim(v) for k, v in s.attrs.items()},
    }
    evs = [[n, t - base_ns, {k: _prim(v) for k, v in a.items()}]
           for n, t, a in list(s.events)]
    if evs:
        d["e"] = evs
    if depth > 0 and s.children:
        d["c"] = [_span_doc(c, base_ns, depth - 1) for c in list(s.children)]
    elif s.children:
        d["pruned"] = len(s.children)
    return d


def serialize_subtree(root: Span, max_bytes: int = 48_000) -> str:
    """One span tree as a compact, header-safe string (JSON → zlib →
    base64). Timestamps ship RELATIVE to the root's start, so the clock
    domains of two hosts never need to agree. Oversized trees prune the
    deepest levels first until the encoding fits ``max_bytes``."""
    for depth in (64, 6, 3, 1, 0):
        doc = _span_doc(root, root.t0_ns, depth)
        enc = base64.b64encode(
            zlib.compress(json.dumps(doc, separators=(",", ":")).encode())
        ).decode("ascii")
        if len(enc) <= max_bytes:
            return enc
    return enc  # depth 0: a single span always fits in practice


def _build_span(doc: dict, trace_id: str, base_ns: int) -> Span:
    sp = Span(str(doc.get("n", "?")), dict(doc.get("a") or {}), None)
    sp.trace_id = trace_id
    sp.span_id = str(doc.get("i", sp.span_id))
    sp.thread_id = int(doc.get("th", 0))
    sp.t0_ns = base_ns + int(doc.get("o", 0))
    sp.t1_ns = sp.t0_ns + int(doc.get("d", 0))
    for n, t, a in doc.get("e", ()):
        sp.events.append((str(n), base_ns + int(t), dict(a)))
    if doc.get("pruned"):
        sp.attrs["children_pruned"] = int(doc["pruned"])
    for c in doc.get("c", ()):
        child = _build_span(c, trace_id, base_ns)
        child.parent_id = sp.span_id
        sp.children.append(child)
    return sp


# inflated-payload ceiling for remote-supplied subtrees: a 64 KB header
# (http.client's line limit) crafted as a zlib bomb must not expand into
# hundreds of MB on the client — decompression stops at this many bytes
_MAX_INFLATED_BYTES = 4 * 1024 * 1024


def _decode_subtree_doc(encoded: str) -> dict:
    d = zlib.decompressobj()
    raw = d.decompress(base64.b64decode(encoded), _MAX_INFLATED_BYTES)
    if d.unconsumed_tail:
        raise ValueError(
            f"serialized span subtree inflates past {_MAX_INFLATED_BYTES} B")
    return json.loads(raw.decode())


def deserialize_subtree(encoded: str, trace_id: str = "",
                        base_ns: int = 0) -> Span:
    """Inverse of :func:`serialize_subtree`: a real :class:`Span` tree
    (walk/find/exporters all work), re-anchored at ``base_ns``."""
    return _build_span(_decode_subtree_doc(encoded), trace_id, base_ns)


def graft_serialized(parent: Span, encoded: str) -> Span | None:
    """Graft a remote member's serialized subtree under the local RPC
    span: the remote root becomes a child of ``parent``, its ids rebased
    onto the parent's trace and its clock re-anchored inside the RPC
    window (centered — the residual on either side reads as network
    time). Returns the grafted root, or None on a malformed payload."""
    try:
        doc = _decode_subtree_doc(encoded)
    except Exception:  # noqa: BLE001 — a torn header must not fail the call
        return None
    elapsed = (parent.t1_ns or time.perf_counter_ns()) - parent.t0_ns
    remote_dur = int(doc.get("d", 0))
    base = parent.t0_ns + max((elapsed - remote_dur) // 2, 0)
    root = _build_span(doc, parent.trace_id, base)
    root.parent_id = parent.span_id
    parent.children.append(root)
    return root


def recent() -> list:
    """Completed root spans, oldest first (non-destructive)."""
    with _buffer_lock:
        return list(_traces)


def find_trace(trace_id: str) -> "Span | None":
    """The completed root span with this trace_id (newest wins), or None.
    Exemplar resolution: the query lens's tail buckets retain trace ids
    (obs/lens.py); this turns one back into its stitched span tree while
    it is still inside the completed-roots ring."""
    if not trace_id:
        return None
    with _buffer_lock:
        for root in reversed(_traces):
            if root.trace_id == trace_id:
                return root
    return None


def span_doc(root: "Span", max_depth: int = 64) -> dict:
    """One span tree as plain JSON — the web layer's exemplar-resolution
    payload (``GET /api/obs/lens?trace=``). Same compact keys as the
    federation wire doc (n/i/o/d/a/e/c, offsets relative to the root's
    start) plus the absolute anchor so clients can line trees up."""
    d = _span_doc(root, root.t0_ns, max_depth)
    d["trace_id"] = root.trace_id
    d["t0_ns"] = root.t0_ns
    return d


def drain() -> list:
    """Completed root spans, clearing the buffer (exporter consumption)."""
    with _buffer_lock:
        out = list(_traces)
        _traces.clear()
    return out


class StageTimeline:
    """A root span flattened to the stage decomposition the acceptance
    contract names: direct children as (stage, ms) pairs plus an ``other``
    residual. Child durations are CLAMPED to the root's own window —
    a still-open child (an abandoned, timed-out scan worker whose span
    never closed) or one attached late cannot push coverage past wall —
    so for the sequential query pipeline stage durations sum to wall time
    by construction (``other`` absorbs untraced gaps)."""

    def __init__(self, root: Span):
        self.root = root
        self.wall_ms = root.duration_ms
        root_end = root.t1_ns if root.t1_ns else time.perf_counter_ns()
        stages = []
        for c in list(root.children):
            child_end = c.t1_ns if c.t1_ns else root_end  # still open
            lo = max(c.t0_ns, root.t0_ns)
            hi = min(child_end, root_end)
            stages.append((c.name, max(hi - lo, 0) / 1e6))
        covered = sum(ms for _, ms in stages)
        other = self.wall_ms - covered
        if other > 1e-6:
            stages.append(("other", other))
        self.stages = stages

    def stage_ms(self, name: str) -> float:
        return sum(ms for n, ms in self.stages if n == name)

    def render(self) -> str:
        lines = [
            f"Stage timeline ({self.wall_ms:.3f} ms wall, "
            f"trace {self.root.trace_id}):"
        ]
        for n, ms in self.stages:
            pct = 100.0 * ms / self.wall_ms if self.wall_ms else 0.0
            lines.append(f"  {n:<12s} {ms:10.3f} ms  {pct:5.1f}%")
        return "\n".join(lines)

    __str__ = render


# operator opt-in without code: enabling via the environment here means
# child worker processes inherit tracing
if os.environ.get("GEOMESA_TPU_TRACE"):
    enable()
