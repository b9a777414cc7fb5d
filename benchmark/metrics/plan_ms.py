"""Planning (``planning/``, ``index/``): mean time of the ``plan`` span,
its ``decompose`` children included, per CQL answered."""


def read(ctx):
    spans = [s for r in ctx.roots for s in r.find("plan")]
    if not spans or not ctx.queries:
        return None
    return sum(s.duration_ms for s in spans) / ctx.queries
