"""Device-side spatial redistribution: route rows to their z-range owner
shard with ``all_to_all`` over the mesh.

Role parity: the reference redistributes data by writing into a range-
partitioned sorted map (tablets split/migrate server-side — SURVEY.md §2.20
P1/P2); Spark-side spatial joins shuffle rows between executors. TPU-native,
the shuffle is one ``all_to_all`` over ICI inside ``shard_map``: each device
bins its resident rows by the target split points (``store/splitter.py``),
packs fixed-capacity per-destination buffers, exchanges them collectively,
and locally sorts what it received. This is the multi-chip ingest/compaction
path and the redistribution primitive for spatial joins (SURVEY.md §5
"all_to_all for spatial-join redistribution").

Fixed shapes: capacity per (source → destination) lane is a compile-time
bound; rows beyond it are counted in the returned ``overflow`` (caller
re-runs with a bigger capacity — balanced splits keep the default ample).
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from geomesa_tpu.parallel.mesh import DATA_AXIS, Mesh, data_shards

__all__ = ["make_reshard_step", "reshard"]

# The routing key IS a u64 z-code by contract (reshard sorts the store's
# native key dtype); on TPU this whole module runs the documented
# emulated-64-bit path. The uint32-pair migration is a tracked redesign,
# not a local fix.
_SENTINEL = jnp.uint64(0xFFFFFFFFFFFFFFFF)  # tpulint: disable=J004


@lru_cache(maxsize=None)
def make_reshard_step(mesh: Mesh, n_columns: int, capacity: int,
                      lex_cols: int = 0):
    """Build the jitted reshard step for ``n_columns`` int32 payload columns.

    fn(key_u64, true_n, splits, *cols) →
        (key_out, cols_out, count_per_shard, overflow) where outputs are
        device-sharded (S × S·capacity rows), each shard's first ``count``
        rows key-sorted and owned by that shard's split range.

    ``lex_cols``: the first that-many payload columns act as SECONDARY sort
    keys after the routing key (applied right-to-left with stable sorts), so
    a composite key wider than 64 bits — e.g. z3's (bin, 63-bit z) — routes
    by a coarse uint64 prefix yet lands exactly lexsorted.
    """
    shards = data_shards(mesh)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(DATA_AXIS),
            P(),
            P(),
            *([P(DATA_AXIS)] * n_columns),
        ),
        out_specs=(
            P(DATA_AXIS),
            *([P(DATA_AXIS)] * n_columns),
            P(DATA_AXIS),
            P(),
        ),
        check_vma=False,
    )
    def step(key, true_n, splits, *cols):
        nloc = key.shape[0]
        sid = jax.lax.axis_index(DATA_AXIS)
        base = sid * nloc
        valid = (base + jnp.arange(nloc, dtype=jnp.int32)) < true_n

        owner = jnp.searchsorted(splits, key, side="right").astype(jnp.int32)
        owner = jnp.where(valid, owner, shards)  # padding → overflow group

        order = jnp.argsort(owner, stable=True)
        so = owner[order]
        starts = jnp.searchsorted(so, jnp.arange(shards, dtype=jnp.int32))
        rank = jnp.arange(nloc, dtype=jnp.int32) - starts[jnp.clip(so, 0, shards - 1)]
        ok = (so < shards) & (rank < capacity)
        overflow = jnp.sum((so < shards) & (rank >= capacity), dtype=jnp.int32)
        # slot S*capacity is the discard bin (sliced off after scatter)
        slot = jnp.where(ok, so * capacity + rank, shards * capacity)

        def route(arr, fill):
            buf = jnp.full((shards * capacity + 1,), fill, dtype=arr.dtype)
            buf = buf.at[slot].set(arr[order])
            send = buf[: shards * capacity].reshape(shards, capacity)
            recv = jax.lax.all_to_all(send, DATA_AXIS, 0, 0, tiled=False)
            return recv.reshape(shards * capacity)

        key_r = route(key, _SENTINEL)
        got = key_r != _SENTINEL
        count = jnp.sum(got, dtype=jnp.int32)
        cols_r = tuple(route(c, jnp.zeros((), c.dtype)) for c in cols)
        # local order: valid rows lexsorted by (key, lex payload cols),
        # sentinels last. Stable sorts right-to-left = lexsort semantics.
        perm = jnp.arange(key_r.shape[0], dtype=jnp.int32)
        for j in range(lex_cols - 1, -1, -1):
            perm = perm[jnp.argsort(cols_r[j][perm], stable=True)]
        perm = perm[
            jnp.argsort(jnp.where(got, key_r, _SENTINEL)[perm], stable=True)
        ]
        key_out = key_r[perm]
        cols_out = tuple(c[perm] for c in cols_r)
        return (
            key_out,
            *cols_out,
            count.reshape(1),
            jax.lax.psum(overflow, DATA_AXIS),
        )

    return step


def reshard(
    mesh: Mesh,
    key_sharded,
    true_n: int,
    splits: np.ndarray,
    cols: dict,
    capacity: int | None = None,
    lex_cols: int = 0,
):
    """Convenience wrapper: reshard device arrays by ``splits``.

    Returns (key_out, cols_out dict, counts (S,), overflow int). ``capacity``
    (rows per source→destination lane) auto-sizes to 2× the balanced
    per-lane load (+margin); callers retry with a larger one on overflow.
    ``lex_cols``: the first that-many of ``cols`` (insertion order) are
    secondary local-sort keys — see :func:`make_reshard_step`.
    """
    shards = data_shards(mesh)
    nloc = key_sharded.shape[0] // shards
    if capacity is None:
        capacity = max(8, (2 * nloc) // shards + 8)
    step = make_reshard_step(mesh, len(cols), capacity, lex_cols)
    rep = NamedSharding(mesh, P())
    names = list(cols)
    out = step(
        key_sharded,
        jax.device_put(jnp.int32(true_n), rep),
        jax.device_put(jnp.asarray(splits, dtype=key_sharded.dtype), rep),
        *[cols[n] for n in names],
    )
    key_out = out[0]
    cols_out = {n: out[1 + i] for i, n in enumerate(names)}
    counts = np.asarray(out[1 + len(names)])
    overflow = int(out[2 + len(names)])
    return key_out, cols_out, counts, overflow
