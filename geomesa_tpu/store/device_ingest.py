"""Device-side bulk ingest & rebalance: stats-driven splits + all_to_all.

Role parity: ``DefaultSplitter.scala:33`` (stats-driven table cut points) and
the tablet split/migration rebalancing the reference delegates to its storage
layer (SURVEY.md §2.20 P1/P8). TPU-native lifecycle step: rows land on the
mesh in ARRIVAL order (no host sort), split keys are sampled-quantile cuts of
the *resident* keys, and one ``all_to_all`` reshard routes every row to its
z-range owner shard with a local sort — after which per-device row counts are
balanced to within sampling error even for fully skewed geodata (all points
in one hemisphere). Used by the lambda-tier persister when draining the hot
tier and by bulk mesh ingest.
"""

from __future__ import annotations

import numpy as np

import jax

from geomesa_tpu.parallel.mesh import Mesh, data_shards, shard_columns
from geomesa_tpu.parallel.reshard import reshard
from geomesa_tpu.store.splitter import balanced_splits

__all__ = ["sampled_splits", "device_bulk_build", "device_sort_perm"]


def sampled_splits(
    key_sharded, true_n: int, n_shards: int, per_shard_samples: int = 2048
) -> np.ndarray:
    """Stats-driven shard cut points from a strided device-side key sample.

    Pulls ~``per_shard_samples × n_shards`` keys (a few KB) instead of the
    full column — the quantile estimate errs by O(1/samples), far inside the
    10% balance budget.
    """
    total = int(key_sharded.shape[0])
    want = max(per_shard_samples * n_shards, n_shards)
    stride = max(1, true_n // want)
    sample = np.asarray(jax.device_get(key_sharded[:true_n:stride]))
    return balanced_splits(np.sort(sample), n_shards)


def device_bulk_build(mesh: Mesh, keys: np.ndarray, payload: dict):
    """Arrival-order rows → balanced, per-shard-sorted device store.

    ``keys``: (n,) uint64 curve keys in arrival order; ``payload``: int32
    columns riding along. Returns (key_out, cols_out, counts, splits):
    device arrays sharded over the mesh data axis where shard d owns keys in
    ``[splits[d-1], splits[d])``, locally sorted, with ``counts[d]`` real
    rows. Overflowing capacity lanes (badly skewed arrival order) retry with
    doubled capacity — fixed shapes stay compile-cached per capacity.
    """
    key_out, cols_out, counts, splits = _reshard_with_retry(
        mesh, keys, payload
    )
    return key_out, cols_out, counts, splits


def _reshard_with_retry(mesh: Mesh, keys: np.ndarray, payload: dict,
                        lex_cols: int = 0):
    """Shard + split + reshard with capacity-doubling retries on overflow
    (shared by :func:`device_bulk_build` and :func:`device_sort_perm`).
    Raises RuntimeError if overflow persists at full per-shard capacity."""
    n = len(keys)
    shards = data_shards(mesh)
    cols, padded, rows_per_shard = shard_columns(
        mesh, {"key": keys.astype(np.uint64), **payload}
    )
    splits = sampled_splits(cols["key"], n, shards)
    payload_dev = {k: cols[k] for k in payload}
    capacity = None
    for _ in range(8):
        key_out, cols_out, counts, ovf = reshard(
            mesh, cols["key"], n, splits, payload_dev,
            capacity=capacity, lex_cols=lex_cols,
        )
        if ovf == 0:
            return key_out, cols_out, counts, splits
        capacity = (capacity or max(8, (2 * rows_per_shard) // shards + 8)) * 2
        if capacity >= rows_per_shard:
            capacity = rows_per_shard  # one lane can hold a whole shard
    key_out, cols_out, counts, ovf = reshard(
        mesh, cols["key"], n, splits, payload_dev,
        capacity=rows_per_shard, lex_cols=lex_cols,
    )
    if ovf != 0:
        raise RuntimeError(f"reshard overflow persisted at full capacity: {ovf}")
    return key_out, cols_out, counts, splits


def device_sort_perm(
    mesh: Mesh, route_key: np.ndarray, tiebreak: np.ndarray | None = None
) -> np.ndarray:
    """Distributed sample sort on the mesh → the sorting permutation.

    The index-build path's host ``lexsort`` replacement (SURVEY.md §2.20 P1,
    the ``DefaultSplitter.scala:33`` stats-driven-cuts role made a device
    primitive): rows route to their key-range owner shard via stats-driven
    ``sampled_splits`` + one ``all_to_all``, each shard sorts locally, and
    concatenating shards in split order yields the global sort. Composite
    keys wider than 64 bits (z3's (bin, 63-bit z)) pass the high bits as
    ``route_key`` and the low bits as ``tiebreak`` — the reshard step
    lexsorts by (route_key, tiebreak), which equals the exact wide-key
    order whenever ``route_key`` is a monotone prefix of it.

    Returns a (n,) int64 permutation with the same row-set semantics as the
    host sort (tie ORDER between fully-equal keys may differ; all sorted key
    products are identical). Raises ValueError for inputs the device path
    cannot represent (≥ int32 rows; a route key equal to the reshard padding
    sentinel, which would silently drop the row) and RuntimeError on
    persistent reshard overflow — callers fall back to the host sort.
    """
    n = len(route_key)
    if n >= 2**31:
        raise ValueError("device_sort_perm: > int32 rows per build")
    if n and int(route_key.max()) == 2**64 - 1:
        raise ValueError("device_sort_perm: route key collides with sentinel")
    shards = data_shards(mesh)
    # no tiebreak sorts by an all-zero one: every build then shares ONE
    # compiled reshard program per shape (its sorts take minutes to
    # compile for the TPU; the zero column costs one int32 pass)
    tie = (np.zeros(n, np.int32) if tiebreak is None
           else tiebreak.astype(np.int32))
    payload = {"tie": tie, "rowid": np.arange(n, dtype=np.int32)}
    _, cols_out, counts, _splits = _reshard_with_retry(
        mesh, route_key, payload, lex_cols=1
    )
    # per-shard sorted rowids, concatenated in shard order = global sort.
    # cols_out["rowid"] is (S * S*capacity) device-sharded; shard d's first
    # counts[d] rows are real.
    rid = np.asarray(jax.device_get(cols_out["rowid"]))
    per_shard = rid.reshape(shards, -1)
    return np.concatenate(
        [per_shard[d, : int(counts[d])] for d in range(shards)]
    ).astype(np.int64)
