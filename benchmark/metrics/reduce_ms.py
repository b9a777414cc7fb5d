"""Host result path (``store/datastore.py``): mean self-time of the
``refine`` and ``reduce`` spans per CQL answered (a span's duration less
its children's)."""


def _self_ms(span):
    return span.duration_ms - sum(c.duration_ms for c in list(span.children))


def read(ctx):
    spans = [s for r in ctx.roots for name in ("refine", "reduce")
             for s in r.find(name)]
    if not spans or not ctx.queries:
        return None
    return sum(_self_ms(s) for s in spans) / ctx.queries
