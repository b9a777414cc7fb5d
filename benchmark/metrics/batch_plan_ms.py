"""Planning of a batch (``store/datastore.py`` ``_batch_payloads``):
total time of the ``plan`` spans that are children of a ``count_many``
span — every CQL's parse, extraction and payload for the call — per CQL
answered. A ``plan`` span under ``query`` is ``plan_ms``'s."""


def read(ctx):
    spans = [c for r in ctx.roots for s in r.find("count_many")
             for c in list(s.children) if c.name == "plan"]
    if not spans or not ctx.queries:
        return None
    return sum(s.duration_ms for s in spans) / ctx.queries
