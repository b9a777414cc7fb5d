"""Set-up and health of the system under test.

Copied from ``chip_smoke.py`` so that no later PR moves them: the store
build (``FeatureTable.from_columns`` → ``DataStore`` on an explicit mesh
of the cell's ``chips`` devices → ``write``, which compacts past its
threshold, → ``compact``) and the residency check after it, the compile
clock, the device-health counters and the peak-HBM reading.
"""

from __future__ import annotations

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class CompileClock:
    """Sums JAX's own compile-phase durations and counts backend compiles
    (a persistent-cache hit is a short one) and cache hits/misses."""

    def __init__(self):
        import jax.monitoring as jm

        self.secs = dict.fromkeys(_COMPILE_EVENTS.values(), 0.0)
        self.backend_compiles = 0
        self.events = {"cache_hits": 0, "cache_misses": 0}
        jm.register_event_duration_secs_listener(self._on_duration)
        jm.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        key = _COMPILE_EVENTS.get(name)
        if key:
            self.secs[key] += secs
            if key == "backend_compile_s":
                self.backend_compiles += 1

    def _on_event(self, name, **_kw):
        tail = name.rsplit("/", 1)[-1]
        if tail in self.events:
            self.events[tail] += 1

    def report(self) -> dict:
        return {**self.secs, "backend_compiles": self.backend_compiles,
                **self.events}


class NotResident(RuntimeError):
    """The index does not live on the devices the cell asks for."""


def build_store(cfg: dict, data, chips: int):
    """A ``DataStore`` on a mesh of the first ``chips`` devices holding
    ``data`` under the configuration's spec, written and compacted as a
    bulk ingest is. Raises ``NotResident`` unless the z3 index then lives
    on exactly ``chips`` devices: a store on fewer is not the deployment."""
    from geomesa_tpu.parallel.mesh import make_mesh
    from geomesa_tpu.schema.columnar import Column, FeatureTable, point_column
    from geomesa_tpu.schema.sft import parse_spec
    from geomesa_tpu.store.backends import TpuBackend
    from geomesa_tpu.store.datastore import DataStore

    sft = parse_spec(cfg["type_name"], cfg["spec"])
    cols = {}
    for a in sft.attributes:
        if a.name == sft.geom_field:
            cols[a.name] = point_column(data.lon, data.lat)
        elif a.name == sft.dtg_field:
            cols[a.name] = Column(a.type, data.t)
        else:
            cols[a.name] = Column(a.type, data.attrs[a.name])
    table = FeatureTable.from_columns(sft, data.fids, cols)
    # an explicit mesh: the run uses exactly `chips` devices even on a
    # host with more
    ds = DataStore(backend=TpuBackend(mesh=make_mesh(chips)))
    ds.create_schema(sft)
    ds.write(cfg["type_name"], table)
    ds.compact(cfg["type_name"])
    ids = residency(ds, cfg["type_name"])
    if len(ids) != chips:
        ds.close()
        raise NotResident(f"z3 index resident on devices {ids}, want {chips}")
    return ds


def residency(ds, type_name: str, index: str = "z3") -> list:
    """Ids of the devices holding the resident index's columns."""
    return sorted({d.id for c in resident_columns(ds, type_name, index).values()
                   for d in c.devices()})


def mesh_devices(ds) -> list:
    """The devices of the store's mesh."""
    return list(ds.backend._get_mesh().devices.flat)


def resident_columns(ds, type_name: str, index: str = "z3") -> dict:
    """The device arrays of one resident index, by column name."""
    state = ds._state(type_name).backend_state or {}
    dev = state.get(index)
    return {} if dev is None else dict(dev.cols)


def device_health(ds) -> dict:
    m = ds.metrics
    return {
        "device_failovers": m.counter("store.query.device_failovers").count,
        "sort_failures": m.counter("store.device.sort_failures").count,
        "load_failures": m.counter("store.device.load_failures").count,
        "circuit_open": int(bool(m.gauge("store.device.circuit_open").value)),
    }


def peak_hbm(devices) -> list:
    """``peak_bytes_in_use`` of each device, None where the backend
    reports none (the CPU)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
