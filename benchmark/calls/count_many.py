"""``DataStore.count_many(cqls, loose=True)``: a dashboard's panel counts.

Loose bbox is GeoMesa's default query semantics: counts are taken in the
store's int key domain (31-bit coordinates, 1 s offsets in the z3 week
bin), a superset of the exact answer by at most those quanta. Each count
must lie between the reference's exact count and its count over the box
and window widened by more than one quantum.
"""

from __future__ import annotations

# every count inside its (exact, widened) bounds; a missing count is out
LIMITS = {"counts_out_of_bounds": 0}


def send(ds, type_name, cqls):
    return [int(c) for c in ds.count_many(type_name, cqls, loose=True)]


def keep(answer):
    return answer


def expected(referee, request):
    return [referee.loose_bounds(box, window) for box, window, _ in request]


def control(referee32, request):
    return [referee32.count(box, window) for box, window, _ in request]


def judge(kept, want, fid_base=0) -> dict:
    out = sum(1 for got, (lo, hi) in zip(kept, want) if not lo <= got <= hi)
    return {"counts_out_of_bounds": out + abs(len(want) - len(kept))}
