"""Per-query telemetry (``DataStore._audit``: flight record, SLO, lens,
ledger rollup, usage metering, cost table): total time of the program's
``telemetry`` spans per CQL answered."""


def read(ctx):
    spans = [s for r in ctx.roots for s in r.find("telemetry")]
    if not spans or not ctx.queries:
        return None
    return sum(s.duration_ms for s in spans) / ctx.queries
