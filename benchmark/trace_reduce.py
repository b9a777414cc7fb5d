"""From a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing of the program:

- device op events: every event on the device planes' op lines
  (``/device:TPU:<n>``, line ``XLA Ops``); their union is the busy time;
- host anchors: the ``bench.request`` annotations the harness opens
  around each request, which put the program's own spans (timed on
  ``perf_counter``) on the trace's clock;
- the window: from the first anchor's start to the last anchor's end.

``breakdown`` gives the device ops that took most time, and the device's
idle time inside the window summed by the host span open at the time
(the innermost program span, ``client`` between requests), each gap
split where that span changes.

Over several chips (one device plane each): ``busy_s`` and the idle
labels are means per chip, so the labels add up to ``window_s -
busy_s``; ``op_seconds`` and ``breakdown.device_ops`` are summed over the
chips, chip-seconds. A roofline share that divides a call's global bytes
by its ops' seconds summed over chips is therefore the per-chip share.
With one plane every number is that chip's own.
"""

from __future__ import annotations

import glob
from collections import defaultdict
from pathlib import Path

import numpy as np

ANCHOR = "bench.request"
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(trace_dir) -> str | None:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and not p.name.startswith("/device:CPU")]


def op_name(event_name: str) -> str:
    """The HLO instruction's name (``%batched_count.1 = s32[..] ...`` →
    ``batched_count.1``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_kind(event_name: str) -> str:
    """The instruction's name less its numeric suffix: a Pallas kernel is
    named after its ``pallas_call`` (``batched_count``)."""
    name = op_name(event_name)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def device_ops(pd) -> list:
    """(op name, start_ns, end_ns, plane) of every device op event."""
    out = []
    for plane in _device_planes(pd):
        for ln in plane.lines:
            if ln.name == OPS_LINE:
                out.extend((op_name(e.name), e.start_ns, e.end_ns, plane.name)
                           for e in ln.events)
    return out


def anchors(pd) -> list:
    """(start_ns, end_ns) of the harness's request annotations, in order."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            out.extend((e.start_ns, e.end_ns) for e in ln.events
                       if e.name == ANCHOR)
    return sorted(out)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    busy = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) of the device's idle stretches inside ``[lo, hi]``."""
    gaps = []
    at = lo
    for s, e in sorted(intervals):
        if e <= at:
            continue
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def span_intervals(roots, offset_ns: float) -> list:
    """(start, end, depth, name) of every program span, on the trace clock."""
    out = []

    def walk(sp, depth):
        if sp.t1_ns:
            out.append((sp.t0_ns + offset_ns, sp.t1_ns + offset_ns, depth,
                        sp.name))
        for c in list(sp.children):
            walk(c, depth + 1)

    for r in roots:
        walk(r, 0)
    return out


def label_segments(spans) -> list:
    """(start, end, label): the innermost program span open over each
    stretch of time (``client`` where none is). Spans of one request nest,
    and requests follow one another, so a stack sweeps them."""
    bounds = sorted([(s, 1, i) for i, (s, _, _, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, e, _, _) in enumerate(spans)])
    out, stack, at = [], [], None
    for t, is_start, i in bounds:
        if at is not None and t > at:
            out.append((at, t, spans[stack[-1]][3] if stack else "client"))
        at = t
        if is_start:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    return out


def gaps_by_label(gaps, segments) -> dict:
    """Seconds of each idle gap under each label, split where the label
    changes inside the gap (both lists in time order)."""
    out = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(segments) and segments[j][1] <= gs:
            j += 1
        k, at = j, gs
        while at < ge:
            if k < len(segments) and segments[k][0] <= at:
                end = min(ge, segments[k][1])
                out[segments[k][2]] += (end - at) / 1e9
                at = end
                if end == segments[k][1]:
                    k += 1
            else:
                end = min(ge, segments[k][0]) if k < len(segments) else ge
                out["client"] += (end - at) / 1e9
                at = end
    return out


def reduce(path: str, request_starts_ns=(), roots=()) -> dict | None:
    """Busy and window seconds, op seconds by name, and the breakdown.

    ``request_starts_ns``: the harness's ``perf_counter_ns`` at each
    request's start, in order, matched to the anchors to place ``roots``
    (the program's span trees, one a request) on the trace clock.
    Returns None where the trace holds no device plane (the CPU).
    """
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops = device_ops(pd)
    marks = anchors(pd)
    if not ops or not marks:
        return None
    lo, hi = marks[0][0], marks[-1][1]
    n_planes = len({o[3] for o in ops})
    by_plane = defaultdict(list)
    for _, s, e, plane in ops:
        by_plane[plane].append((s, e))
    busy = sum(union_ns(iv, lo, hi) for iv in by_plane.values()) / n_planes
    op_s = defaultdict(float)
    for name, s, e, _ in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            op_s[name] += (e - s) / 1e9
    out = {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
           "op_seconds": dict(op_s), "ops": ops, "window_ns": (lo, hi)}
    gaps_by = {}
    n = min(len(marks), len(request_starts_ns))
    if n:
        offset = float(np.median([marks[i][0] - request_starts_ns[i]
                                  for i in range(n)]))
        segments = label_segments(span_intervals(roots, offset))
        gaps_by = defaultdict(float)
        for iv in by_plane.values():
            for k, v in gaps_by_label(idle_gaps(iv, lo, hi), segments).items():
                gaps_by[k] += v / n_planes
    out["breakdown"] = {
        "device_ops": sorted(([k, v] for k, v in op_s.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(([k, v] for k, v in gaps_by.items()),
                            key=lambda kv: -kv[1])[:TOP],
    }
    return out
