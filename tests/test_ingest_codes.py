"""Ingest through dictionary codes: the string columns' hash factorization
against the plain ``np.unique`` path it replaced.

``Column.dictionary()`` factorizes by hashing and sorts only the distinct
values; ``StoreStats.rebuild`` counts codes instead of sorting objects;
``AttributeIndex.build`` sorts by rank codes instead of strings. Each is
held here to the reference: the ``np.unique`` path, kept in this file.
"""

import numpy as np
import pytest

from geomesa_tpu import obs
from geomesa_tpu.geometry import Point
from geomesa_tpu.index.attribute import (
    AttributeIndex,
    _sort_surrogate,
    stable_lexsort,
)
from geomesa_tpu.schema.columnar import Column, FeatureTable
from geomesa_tpu.schema.sft import AttributeType, parse_spec
from geomesa_tpu.stats.sketches import _hash_basis
from geomesa_tpu.stats.store_stats import AttributeStats, StoreStats
from geomesa_tpu.store.datastore import DataStore

T0 = 1_498_867_200_000


# -- the reference: the np.unique path ---------------------------------------
def reference_dictionary(col):
    """np.unique over the column's unicode cast, non-str values as ""."""
    if col.valid is None and all(type(v) is str for v in col.values):
        flat = np.asarray(col.values, dtype=str)
    else:
        flat = np.array(
            [v if isinstance(v, str) else "" for v in col.values], dtype=object
        ).astype(str)
    vocab, codes = np.unique(flat, return_inverse=True)
    return vocab, codes.astype(np.int32)


def reference_basis(values):
    return np.array(
        [np.uint64(hash(v) & 0xFFFFFFFFFFFFFFFF) for v in values],
        dtype=np.uint64,
    )


def reference_string_stats(col):
    """One string column's sketches by np.unique over the set objects."""
    vals = col.values[col.is_valid()]
    st = AttributeStats()
    if len(vals):
        st.minmax.observe(vals)
    try:
        u, c = np.unique(vals, return_counts=True)
    except TypeError:
        st.frequency.observe(vals)
        st.topk.observe(vals)
        st.cardinality.observe(vals)
        return st
    st.frequency.observe_weighted(u, c)
    st.topk.observe_weighted(u, c)
    st.cardinality.observe(u)
    return st


def reference_perm(ix, table):
    """The attribute index's permutation sorted on the string objects."""
    col = table.columns[ix.attribute]
    valid = col.is_valid()
    key = _sort_surrogate(col.values, valid)
    if not ix.tiered:
        return stable_lexsort([key, ~valid])
    geom = table.geom_column()
    bins, offs = ix.binned.to_bin_and_offset(table.dtg_millis())
    z = ix.sfc.index(geom.x, geom.y, offs)
    return stable_lexsort([z, bins, key, ~valid])


def string_column(values, valid=None):
    arr = np.empty(len(values), dtype=object)
    arr[:] = list(values)
    return Column(AttributeType.STRING, arr, valid)


def _cases():
    rng = np.random.default_rng(32)
    words = np.array([f"w{i:03d}" for i in range(50)], dtype=object)
    drawn = list(words[rng.integers(0, 50, 400)])
    masked = [None if i % 7 == 0 else v for i, v in enumerate(drawn)]
    mask = np.array([v is not None for v in masked])
    with_empty = [("" if i % 5 == 0 else v) for i, v in enumerate(masked)]
    return {
        "all_str": (drawn, None),
        "valid_mask": (masked, mask),
        "real_empty": (with_empty, mask),
        "stray_non_str": (["b", 5, None, 3.5, "a", "", "b"], None),
        "empty": ([], None),
        "single_value": (["only"] * 64, None),
        "all_unique": ([f"{600_000_000 + i:09d}" for i in range(300)], None),
        "nul_chars": (["a", "a\x00b", "a\x00", "b", "a", "a\x00b"], None),
        "non_ascii": (["é", "z", "ä", "\U0001f600", "Z", "é"], None),
        "np_str": ([np.str_("x"), "y", np.str_("x"), "a"], None),
    }


CASES = _cases()


class TestDictionary:
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_equals_np_unique(self, case):
        values, valid = CASES[case]
        col = string_column(values, valid)
        vocab, codes = col.dictionary()
        ref_vocab, ref_codes = reference_dictionary(string_column(values, valid))
        assert vocab.dtype == ref_vocab.dtype
        assert np.array_equal(vocab, ref_vocab)
        assert codes.dtype == np.int32
        assert np.array_equal(codes, ref_codes)
        assert col.dictionary() is not None
        assert col.dictionary()[1] is codes  # cached on the column

    def test_unicode_dtype_column(self):
        vals = np.array(["b", "a", "b", ""], dtype=str)
        col = Column(AttributeType.STRING, vals)
        vocab, codes = col.dictionary()
        ref_vocab, ref_codes = np.unique(vals, return_inverse=True)
        assert np.array_equal(vocab, ref_vocab) and vocab.dtype == ref_vocab.dtype
        assert np.array_equal(codes, ref_codes)
        assert col.ranked() is None  # not objects: the np.unique path stays

    @pytest.mark.parametrize("case,expect", [
        ("all_str", True), ("valid_mask", True), ("real_empty", True),
        ("single_value", True), ("all_unique", True), ("non_ascii", True),
        ("stray_non_str", False),  # a set value that is not a str
        ("nul_chars", False),  # "a" and "a\x00" share a unicode vocab entry
    ])
    def test_ranked_applies(self, case, expect):
        values, valid = CASES[case]
        col = string_column(values, valid)
        ranked = col.ranked()
        assert (ranked is not None) is expect
        if ranked is not None:
            distinct, codes = ranked
            set_vals = col.values[col.is_valid()]
            ref = np.unique(set_vals) if len(set_vals) else set_vals
            seen = np.bincount(codes[col.is_valid()], minlength=len(distinct)) > 0
            assert distinct[seen].tolist() == ref.tolist()

    def test_ranked_none_for_numbers(self):
        assert Column(AttributeType.INT, np.arange(5, dtype=np.int32)).ranked() is None


class TestHashBasis:
    @pytest.mark.parametrize("values", [
        np.array(["a", "b", "", "é", "a\x00b"] + [f"k{i}" for i in range(200)],
                 dtype=object),
        np.array(["x", "yy", "zzz"], dtype=str),
        ["solo"],
        np.array([1, "a", None, 2.5], dtype=object),
    ], ids=["object", "unicode", "list", "mixed_objects"])
    def test_bits_equal_per_value_hash(self, values):
        got = _hash_basis(values)
        assert got.dtype == np.uint64
        assert np.array_equal(got, reference_basis(values))


# -- statistics ----------------------------------------------------------------
STATS_SPEC = (
    "name:String,code:String,uid:String,tag:String,odd:String,n:Integer,"
    "dtg:Date,*geom:Point;geomesa.z3.interval='week'"
)


def stats_table(n=1200, seed=3):
    rng = np.random.default_rng(seed)
    sft = parse_spec("s", STATS_SPEC)
    names = [f"actor {i}" for i in range(60)]
    recs = []
    for i in range(n):
        recs.append({
            "name": None if i % 9 == 0 else names[int(rng.integers(0, 60))],
            "code": None if i % 4 == 0 else ("" if i % 11 == 0 else f"C{i % 7}"),
            "uid": f"{700_000_000 + i:09d}",
            "tag": None,  # no set value at all
            # set values that are not str (bytes): the np.unique path stays
            "odd": None if i % 6 == 0 else f"o{i % 3}".encode(),
            "n": None if i % 13 == 0 else int(rng.integers(0, 100)),
            "dtg": T0 + int(rng.integers(0, 20 * 86_400_000)),
            "geom": Point(float(rng.uniform(-180, 180)),
                          float(rng.uniform(-90, 90))),
        })
    return sft, FeatureTable.from_records(sft, recs, [f"s.{i}" for i in range(n)])


class TestStoreStats:
    @pytest.fixture(scope="class")
    def built(self):
        sft, table = stats_table()
        stats = StoreStats(sft)
        stats.rebuild(table)
        return sft, table, stats

    @pytest.mark.parametrize("attr", ["name", "code", "uid", "tag", "odd"])
    def test_string_sketches_identical(self, built, attr):
        _, table, stats = built
        got = stats.attrs[attr]
        ref = reference_string_stats(table.columns[attr])
        assert np.array_equal(got.frequency.table, ref.frequency.table)
        assert np.array_equal(got.cardinality.registers, ref.cardinality.registers)
        assert list(got.topk.counts.items()) == list(ref.topk.counts.items())
        assert [type(k) for k in got.topk.counts] == [type(k) for k in ref.topk.counts]
        for side in ("min", "max"):
            g, r = getattr(got.minmax, side), getattr(ref.minmax, side)
            assert g == r and type(g) is type(r)
        assert got.histogram is None and got.descriptive is None

    def test_codes_path_taken(self, built):
        _, table, _ = built
        for attr in ("name", "code", "uid", "tag"):
            assert table.columns[attr].ranked() is not None, attr
        assert table.columns["odd"].ranked() is None  # keeps np.unique

    def test_single_value_count_reads_the_same_basis(self, built):
        _, table, stats = built
        vals = table.columns["name"].values
        n7 = sum(1 for v in vals if v == "actor 7")
        # the count-min sketch never undercounts: the single-value path
        # hashes as the bulk observe did
        assert stats.attrs["name"].frequency.count("actor 7") >= n7 > 0
        assert stats.min_max("name").min == min(v for v in vals if v is not None)


# -- attribute indexes ----------------------------------------------------------
def attr_table(spec, n=900, seed=5):
    rng = np.random.default_rng(seed)
    sft = parse_spec("a", spec)
    names = [f"name{i}" for i in range(30)] + ["", "é"]
    recs = []
    for i in range(n):
        r = {
            "name": None if i % 8 == 0 else names[int(rng.integers(0, len(names)))],
            "geom": Point(float(rng.uniform(-180, 180)),
                          float(rng.uniform(-90, 90))),
        }
        if sft.dtg_field is not None:
            r["dtg"] = T0 + int(rng.integers(0, 40 * 86_400_000))
        recs.append(r)
    return sft, FeatureTable.from_records(sft, recs, [f"a.{i}" for i in range(n)])


ATTR_SPECS = {
    "tiered": "name:String:index=true,dtg:Date,*geom:Point;geomesa.z3.interval='week'",
    "plain": "name:String:index=true,*geom:Point",
}


class TestAttributeIndex:
    @pytest.mark.parametrize("kind", sorted(ATTR_SPECS))
    def test_build_identical(self, kind):
        sft, table = attr_table(ATTR_SPECS[kind])
        ix = AttributeIndex(sft, "name")
        assert ix.tiered is (kind == "tiered")
        perm = ix.build(table)
        assert table.columns["name"].ranked() is not None  # the codes path ran
        ref = reference_perm(ix, table)
        assert np.array_equal(perm, ref)
        assert np.array_equal(ix.perm, ref)
        vals = table.columns["name"].values
        assert ix.values.dtype == object
        assert ix.values.tolist() == vals[ref].tolist()
        assert ix.n_valid == int(table.columns["name"].is_valid().sum())
        if ix.tiered:
            geom = table.geom_column()
            bins, offs = ix.binned.to_bin_and_offset(table.dtg_millis())
            z = ix.sfc.index(geom.x, geom.y, offs)
            assert np.array_equal(ix.bins, bins[ref])
            assert np.array_equal(ix.zs, z[ref])
        else:
            assert ix.bins is None and ix.zs is None

    @pytest.mark.parametrize("cql,pred", [
        ("name = 'name7'", lambda v: v == "name7"),
        ("name >= 'name2' AND name < 'name4'",
         lambda v: v is not None and "name2" <= v < "name4"),
    ], ids=["equality", "range"])
    def test_queries_match_brute_force(self, cql, pred):
        sft, table = attr_table(ATTR_SPECS["tiered"])
        ds = DataStore(backend="tpu")
        ds.create_schema(sft)
        ds.write("a", table)
        ds.compact("a")
        assert "attr:name" in str(ds.explain("a", cql))
        got = set(ds.query("a", cql).table.fids.tolist())
        want = {f for f, v in zip(table.fids.tolist(),
                                  table.columns["name"].values) if pred(v)}
        assert want and got == want


# -- spans of the build's phases ------------------------------------------------
def test_compacting_write_records_rebuild_spans():
    sft, table = attr_table(ATTR_SPECS["tiered"], n=1500)
    ds = DataStore(backend="tpu")
    ds.create_schema(sft)
    with obs.collect("ingest") as root:
        ds.write("a", table)  # past the compaction threshold: compacts
    (write,) = root.find("write")
    (rebuild,) = write.find("rebuild")
    assert rebuild.attrs["rows"] == 1500
    assert [c.name for c in rebuild.children] == (
        ["index"] * len(ds._state("a").indices) + ["load", "stats"])
    assert [s.attrs["index"] for s in rebuild.find("index")] == list(
        ds._state("a").indices)
    assert "attr:name" in [s.attrs["index"] for s in rebuild.find("index")]
