"""Step programs and kernels: the fused Pallas ``batched_count`` scan's
share of its HBM roofline, in percent.

The least time a call could take is the bytes it must read
(``kernel_bytes.batched_count_bytes``, from the resident columns'
shapes) over the chip's HBM bandwidth (``peaks.json``); the kernel's
time is the device time of its ops in the profiler trace. The scan's
compares run on the VPU, which has no published peak, so HBM is the
only roofline given.
"""

from benchmark.kernel_bytes import batched_count_bytes
from benchmark.trace_reduce import op_kind

KERNEL = "batched_count"  # the pallas_call's name, the HLO op's in the trace


def read(ctx):
    if ctx.trace is None or not ctx.resident:
        return None
    calls = ctx.calls1.get("batched_count", {}).get("calls", 0) - ctx.calls0.get(
        "batched_count", {}).get("calls", 0)
    lo, hi = ctx.trace["window_ns"]
    secs = sum((min(e, hi) - max(s, lo)) / 1e9
               for name, s, e, _ in ctx.trace["ops"]
               if op_kind(name) == KERNEL and min(e, hi) > max(s, lo))
    if calls <= 0 or secs <= 0:
        return None
    moved = batched_count_bytes(ctx.resident) * calls
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / secs
