"""tpuflow contract registry: zero-runtime-cost semantic markers.

The costliest bugs in this repro's history were *semantic contract*
violations invisible to tpulint's local AST rules and tpurace's
locksets: a recreated same-name type serving a dead table's cached
aggregates (ISSUE 7, re-found in ISSUE 15's trajectory cache), audit
shadow traffic training the cost model and burning tenant SLOs
(ISSUE 13), and f64 refinements silently skipped on one of several
routes (ISSUE 8/12). The contracts behind those fixes — "every cache
keyed by a type name dies with the name", "shadow traffic never reaches
a feedback sink", "a cand-band superset post-dominates into an f64
refine" — lived in review checklists. This module turns them into
declarations the live code imports, so the ``--flow`` prong
(:mod:`geomesa_tpu.analysis.flow`) can enforce them on every CI run and
the declarations can never drift from the code they describe.

Every marker is a no-op at runtime: decorators return their argument
unchanged and the module imports nothing but the stdlib, so decorating
a hot-path class costs one function call at import time and zero per
call. The *meaning* is read off the AST by the flow analyzer — these
markers are the vocabulary, ``python -m geomesa_tpu.analysis --flow
--contracts`` is the inventory, and docs/tpulint.md ("Declaring
contracts") is the authoring guide.

Vocabulary:

- :func:`cache_surface` — a derived-data table something else can make
  stale. Declares how entries are keyed and which functions purge it;
  F001 proves every declared mutation path actually reaches a purge,
  that name-keyed caches die on name death (delete/rename), and that
  epoch-keyed caches ride a monotonic epoch.
- :func:`mutation` — a state transition (write/delete/clear/age-off/
  evolve/delete_schema/rename) naming the cache surfaces it must
  invalidate. The F001 reachability source.
- :func:`feedback_sink` — an accumulator that trains or bills off
  observed traffic (cost table, usage meter, SLO burn, workload
  capture, plan-cache store). F002 proves shadow-plane execution cannot
  reach one except through a :func:`shadow_guard` check.
- :func:`shadow_plane` — code whose execution IS audit shadow traffic
  (the auditor, the invariant sweeper, referee execution).
- :func:`shadow_guard` — the recognized discriminators
  (``audit.in_shadow``/``audit.shadow``). A function consulting one is
  shadow-aware: F002 trusts it to gate its own sinks.
- :func:`device_band` — two-band f64 discipline roles: ``certain``
  functions must stay free of f64, ``cand`` results must flow into a
  ``refine`` call (or be returned to a caller that does) — F003.

The dispatch/host-sync budget markers below feed the FOURTH prong,
``--sync`` (:mod:`geomesa_tpu.analysis.sync`), which proves the fusion
work of ROADMAP item 1 statically — worst-case dispatch counts over the
cross-module call graph, host-sync reachability, loop-carried dispatch:

- :func:`dispatch_budget` — an upper bound on device dispatches one
  call may issue (S001), optionally tied to runtime plan signatures so
  ``--sync --reconcile`` can compare the static bound against the
  host-roundtrip ledger's measured counts.
- :func:`host_sync_free` — no host↔device sync is reachable before the
  function returns (S002); intentional awaits retire with a
  ``# tpusync: retire`` comment at the site.
- :func:`choreography_boundary` — the one sanctioned stage-orchestration
  layer: per-item fallback loops inside it are by-design host
  choreography, exempt from S003/S004 and contributing zero dispatch
  cost to callers (budgeted methods of a boundary class opt back in).
"""

from __future__ import annotations

__all__ = [
    "cache_surface", "mutation", "feedback_sink", "shadow_plane",
    "shadow_guard", "device_band", "dispatch_budget", "host_sync_free",
    "choreography_boundary", "MUTATION_KINDS", "DEATH_KINDS",
]

# The mutation classification F001 reasons over. ``DEATH_KINDS`` are the
# name-death transitions: a type NAME stops answering for its old data,
# so everything keyed by the name must be purged (a recreated same-name
# successor restarts the (rebuild epoch, delta version) tuple at equal
# values — epoch stamps alone cannot catch the collision).
MUTATION_KINDS = frozenset({
    "write", "delete", "clear", "age_off", "evolve",
    "delete_schema", "rename",
})
DEATH_KINDS = frozenset({"delete_schema", "rename"})


def cache_surface(*, name, keyed_by, epoch=None, purge=(),
                  immutable=False):
    """Declare a cache surface (stackable; one decorator per surface).

    ``name``: the surface's id — what :func:`mutation` declarations
    reference. ``keyed_by``: what identifies an entry — ``"type_name"``
    (dies with the name: F001 requires a covering DEATH_KINDS mutation),
    ``"epoch"`` (entry validity rides the epoch stamp: ``epoch`` must be
    ``"monotonic"``), or a descriptive key for anything else.
    ``epoch="monotonic"`` asserts the validating stamp can never restart
    at an equal value within the cache's lifetime. ``purge``: functions
    that drop/invalidate entries — bare names resolve to methods of the
    decorated class, ``"Class.method"`` to another class's method,
    ``"pkg.mod:fn"`` to a module-level function. ``immutable=True``
    declares entries are pure functions of their key (compile memos):
    no invalidation contract, inventory only."""

    def deco(obj):
        return obj

    return deco


def mutation(*, kind, invalidates=()):
    """Declare a mutation path: ``kind`` is one of
    :data:`MUTATION_KINDS`; ``invalidates`` names the
    :func:`cache_surface` ids whose purge must be reachable from this
    function through the call graph (F001)."""

    def deco(fn):
        return fn

    return deco


def feedback_sink(fn):
    """Mark an accumulator that trains/bills off observed traffic. F002
    flags any unguarded shadow-plane path into it."""
    return fn


def shadow_plane(obj):
    """Mark a class or function whose execution is audit shadow
    traffic — the F002 taint roots."""
    return obj


def shadow_guard(fn):
    """Mark a recognized shadow discriminator (``in_shadow``/``shadow``).
    A non-root function referencing one is trusted to gate its own
    sinks, so F002 traversal stops there."""
    return fn


def device_band(*, certain=False, cand=False, refine=False):
    """Declare a function's role in the two-band f64 discipline.

    ``certain=True``: produces certain-band device decisions — F003
    flags f64 construction (and refine-band calls) inside it.
    ``cand=True``: produces a candidate-band superset — every call site
    must flow the result into a ``refine`` function or return it to a
    caller that does. ``refine=True``: the exact f64 re-check that
    retires a cand band."""

    def deco(fn):
        return fn

    return deco


def dispatch_budget(n, *, signatures=()):
    """Declare that one call of this function issues at most ``n`` device
    dispatches, worst case, through the whole cross-module call graph
    (a dispatch = one invocation of a ``cached_*_step`` step or a
    jit-compiled ``parallel/query`` callable). ``n`` must be a literal
    int — the ``--sync`` prong computes the structural worst case
    (branches take the max arm, constant-trip loops multiply, a
    non-constant loop around a dispatch is unbounded) and S001 fires
    with the witness chain when it exceeds ``n``.

    ``signatures``: optional :func:`fnmatch.fnmatch` globs over runtime
    plan signatures (``geomesa_tpu.obs.devmon.plan_signature`` — e.g.
    ``"z2:iv16:rows"``; ``"*:rows"`` covers every row-select plan).
    ``--sync --reconcile ledger.json`` matches exported ledger rows
    against these globs and flags any signature whose MEASURED
    dispatches-per-query exceed the declared bound — a divergence means
    a boundary op the static model missed, or a wrong contract."""

    def deco(fn):
        return fn

    return deco


def host_sync_free(fn):
    """Declare that no host↔device synchronization — ``block_until_ready``,
    ``.item()``, ``np.asarray`` of a device value, an implicit
    ``bool()``/``float()`` coercion, ``obs.ledger.materialize`` — is
    reachable through the call graph before this function returns
    (S002). The intentional await that ends a device pipeline retires
    with ``# tpusync: retire`` on the site's line (mirroring F003's
    refine-merge retirement)."""
    return fn


def choreography_boundary(obj):
    """Mark a class or function as the sanctioned stage-orchestration
    layer (the datastore facade): its per-query fallback loops and
    routing are host choreography BY DESIGN. The ``--sync`` prong skips
    S003/S004 inside it and charges callers zero dispatch cost for
    calling into it, so staged paths don't drown the report. A method
    carrying its own :func:`dispatch_budget` opts back into S001."""
    return obj
