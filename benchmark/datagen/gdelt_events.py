"""GDELT v1 events under GeoMesa's predefined GDELT schema, from a seed.

Points are ``bench.py``'s ``synth_gdelt`` model (copied here so no later
PR moves it): half the events in Gaussian clusters around population
centres, half uniform over the inhabited latitudes. Event times are
uniform over the configuration's span and come out in time order, as a
daily export is ingested. Every other attribute of the schema is drawn
from fixed vocabularies: CAMEO-shaped event codes (root 01-20, base,
event) with their quad class and Goldstein value, actors as
(country, role) pairs with names and codes, mention/source/article
counts with a heavy tail, and an average tone.
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen import Dataset, ordinal_ids

ID_BASE = 600_000_000  # GDELT 1.0 event ids of 2017 are 9-digit

# CAMEO root-code shares, roughly those of the GDELT 1.0 event table
# (public statements, consultations and cooperation dominate)
_ROOT_WEIGHTS = np.array([
    14, 9, 8, 16, 9, 3, 3, 2, 3, 2,
    4, 3, 3, 2, 1, 2, 3, 2, 4, 1,
], dtype=np.float64)
_BASES_PER_ROOT = 6
_EVENTS_PER_BASE = 3
_ROLES = np.array(["GOV", "MIL", "COP", "BUS", "MED", "OPP", "JUD", "EDU",
                   "REB", "CVL", "LEG", "NGO", "REF", "SPY", "HLH", "AGR"])
_ROLE_NAMES = np.array([
    "GOVERNMENT", "MILITARY", "POLICE", "BUSINESS", "MEDIA", "OPPOSITION",
    "COURT", "UNIVERSITY", "REBEL", "CIVILIAN", "PARLIAMENT", "AID GROUP",
    "REFUGEE", "INTELLIGENCE", "HOSPITAL", "FARMER"])
_COUNTRIES = 200


def _vocab():
    """The fixed vocabularies (independent of the seed)."""
    rng = np.random.default_rng(20_130_401)
    roots = np.arange(1, 21)
    base = (roots[:, None] * 10 + np.arange(_BASES_PER_ROOT)[None, :]).ravel()
    # event code: the base itself (3 digits) or a 4-digit child of it
    ev_base = np.repeat(base, _EVENTS_PER_BASE)
    child = np.tile(np.arange(_EVENTS_PER_BASE), len(base))
    code_str = np.array(
        [f"{b:03d}" if c == 0 else f"{b:03d}{c}" for b, c in zip(ev_base, child)],
        dtype=object)
    base_str = np.array([f"{b:03d}" for b in ev_base], dtype=object)
    root_of = ev_base // 10
    root_str = np.array([f"{r:02d}" for r in root_of], dtype=object)
    quad = np.select([root_of <= 5, root_of <= 8, root_of <= 13], [1, 2, 3], 4)
    # Goldstein: cooperative roots positive, conflictual negative, per code
    centre = np.interp(root_of, [1, 8, 9, 20], [3.0, 7.0, -2.0, -10.0])
    gold = np.round(np.clip(centre + rng.normal(0, 1.5, len(code_str)),
                            -10, 10), 1)
    w = (_ROOT_WEIGHTS / _ROOT_WEIGHTS.sum())[root_of - 1] / (
        _BASES_PER_ROOT * _EVENTS_PER_BASE)
    # actors: (country, role); names from a fixed letter stream
    letters = np.array(list("ABCDEFGHIJKLMNOPQRSTUVWXYZ"))
    cc = set()
    while len(cc) < _COUNTRIES:
        cc.add("".join(rng.choice(letters, 3)))
    cc = np.array(sorted(cc), dtype=object)
    cname = np.array(["".join(rng.choice(letters, rng.integers(4, 11)))
                      for _ in range(_COUNTRIES)], dtype=object)
    a_cc = np.repeat(cc, len(_ROLES))
    a_code = np.array([c + r for c in cc for r in _ROLES], dtype=object)
    a_name = np.array([n + " " + r for n in cname for r in _ROLE_NAMES],
                      dtype=object)
    # Zipf-like popularity over actors, in a fixed shuffled order
    pop = 1.0 / np.arange(1, len(a_code) + 1) ** 1.1
    pop = pop[rng.permutation(len(pop))]
    return dict(code=code_str, base=base_str, root=root_str, quad=quad,
                gold=gold, code_w=w / w.sum(), a_cc=a_cc, a_code=a_code,
                a_name=a_name, a_w=pop / pop.sum())


def _actor(rng, v, n, empty_share):
    """(name, code, country) columns; ``empty_share`` of rows left blank,
    as GDELT leaves an actor it could not code."""
    idx = rng.choice(len(v["a_code"]), size=n, p=v["a_w"])
    blank = rng.random(n) < empty_share
    out = []
    for col in (v["a_name"], v["a_code"], v["a_cc"]):
        vals = col[idx]
        vals[blank] = ""
        out.append(vals)
    return out


def _sorted_times(rng, n: int, t0: int, span_ms: int) -> np.ndarray:
    """``n`` uniform instants in ``[t0, t0 + span)``, in order, O(n)."""
    gaps = rng.exponential(size=n + 1)
    c = np.cumsum(gaps)
    return t0 + np.floor(c[:-1] / c[-1] * span_ms).astype(np.int64)


def generate(cfg: dict, seed: int) -> Dataset:
    g = cfg["generator_params"]
    n = int(cfg["rows"])
    rng = np.random.default_rng([seed, 1])
    cities = np.asarray(g["cities"], dtype=np.float64)
    k = int(n * g["cluster_share"])
    which = rng.integers(0, len(cities), k)
    lon = np.empty(n)
    lat = np.empty(n)
    sx, sy = g["cluster_sd_deg"]
    lon[:k] = cities[which, 0] + rng.normal(0, sx, k)
    lat[:k] = cities[which, 1] + rng.normal(0, sy, k)
    (x0, x1), (y0, y1) = g["background_lon"], g["background_lat"]
    lon[k:] = rng.uniform(x0, x1, n - k)
    lat[k:] = rng.uniform(y0, y1, n - k)
    np.clip(lon, -180, 180, out=lon)
    np.clip(lat, -90, 90, out=lat)
    # location and time are independent: shuffle the clusters through time
    order = rng.permutation(n)
    lon, lat = lon[order], lat[order]
    t = _sorted_times(rng, n, int(cfg["t0_ms"]),
                      int(cfg["span_days"]) * 86_400_000)

    v = _vocab()
    ev = rng.choice(len(v["code"]), size=n, p=v["code_w"])
    a1 = _actor(rng, v, n, g["actor1_blank_share"])
    a2 = _actor(rng, v, n, g["actor2_blank_share"])
    mentions = (1 + rng.geometric(g["mentions_p"], n)).astype(np.int32)
    sources = np.minimum(mentions, 1 + rng.geometric(0.6, n)).astype(np.int32)
    articles = np.maximum(sources, mentions - rng.integers(0, 2, n)).astype(np.int32)
    tone = np.clip(rng.normal(g["tone_mean"], g["tone_sd"], n), -30.0, 30.0)
    # feature id == globalEventId, as the GDELT converter keys its features
    ids = ordinal_ids(n, ID_BASE, 9)
    attrs = {
        "globalEventId": ids,
        "eventCode": v["code"][ev],
        "eventBaseCode": v["base"][ev],
        "eventRootCode": v["root"][ev],
        "isRootEvent": (rng.random(n) < g["root_event_share"]).astype(np.int32),
        "actor1Name": a1[0], "actor1Code": a1[1], "actor1CountryCode": a1[2],
        "actor2Name": a2[0], "actor2Code": a2[1], "actor2CountryCode": a2[2],
        "quadClass": v["quad"][ev].astype(np.int32),
        "goldsteinScale": v["gold"][ev],
        "numMentions": mentions,
        "numSources": sources,
        "numArticles": articles,
        "avgTone": tone,
    }
    return Dataset(lon=lon, lat=lat, t=t, attrs=attrs, fids=ids, fid_base=ID_BASE)
