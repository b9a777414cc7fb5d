"""``DataStore.query`` of one CQL: a WFS GetFeature.

The answer is the result's ``FeatureTable``; the client holds it once
``query`` returns, and the check keeps its feature ids.
The comparison is exact: the sorted row ids equal the reference's.
"""

from __future__ import annotations

import numpy as np

# exact row sets: any row missing or extra is wrong
LIMITS = {"rows_wrong": 0}


def send(ds, type_name, cqls):
    (cql,) = cqls
    return ds.query(type_name, cql).table


def keep(answer):
    return answer.fids


def expected(referee, request):
    ((box, window, _),) = request
    return referee.rows(box, window)


control = expected


def judge(kept, want, fid_base=0) -> dict:
    got = np.sort(np.asarray(kept).astype(np.int64) - fid_base)
    return {"rows_wrong": int(len(np.setxor1d(got, want, assume_unique=True)))}
