"""Backend (``store/backends.py``): the program's ``QueryLedger``
inter-stage host gap, per query, from the rollup table's change over the
traced window. Only ``query`` and ``select_many`` open a ledger."""


def _totals(export, type_name):
    gap = queries = 0.0
    for e in export.get("entries", []):
        if e["type"] == type_name:
            gap += e["host_gap_ms"]
            queries += e["queries"]
    return gap, queries


def read(ctx):
    g0, q0 = _totals(ctx.ledger0, ctx.type_name)
    g1, q1 = _totals(ctx.ledger1, ctx.type_name)
    if q1 <= q0:
        return None
    return (g1 - g0) / (q1 - q0)
