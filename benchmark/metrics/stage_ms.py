"""Backend staging (``store/backends.py``; ``count_many``'s payload
staging in ``store/datastore.py``): total time of the program's ``stage``
spans — host work between a plan and its first device dispatch — per CQL
answered."""


def read(ctx):
    spans = [s for r in ctx.roots for s in r.find("stage")]
    if not spans or not ctx.queries:
        return None
    return sum(s.duration_ms for s in spans) / ctx.queries
