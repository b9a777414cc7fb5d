"""Backend (``store/backends.py``): device step calls (the jaxmon
``jax.jit.<step>.calls`` census) over the traced window, per CQL
answered."""


def read(ctx):
    if not ctx.queries:
        return None
    calls = sum(v.get("calls", 0) for v in ctx.calls1.values()) - sum(
        v.get("calls", 0) for v in ctx.calls0.values())
    return calls / ctx.queries
