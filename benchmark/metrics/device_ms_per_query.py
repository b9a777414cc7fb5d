"""Step programs and kernels: device busy time in the traced window (the
union of device op intervals in the profiler trace), per CQL answered."""


def read(ctx):
    if ctx.trace is None or not ctx.queries:
        return None
    return ctx.trace["busy_s"] * 1000.0 / ctx.queries
