"""Pallas TPU kernels for the hot scan/encode ops.

The reference pushes its hot loops into the storage servers: the Accumulo
iterator stack / HBase coprocessors run z-filtering + predicate refinement next
to the data (SURVEY.md §2.9), and the Morton interleave lives in the external
``sfcurve`` library (``geomesa-z3/pom.xml:16``). TPU re-design: those loops
become on-chip kernels —

- :func:`batched_count` — the throughput scan (``Z3Iterator`` +
  server-side count role, ``geomesa-index-api/.../index/filters/Z3Filter.scala:
  24-55``): Q bbox+time-window count queries over the shard's sorted columnar
  slice in ONE pass. A 1D grid walks row tiles; each tile is loaded into VMEM
  once and scored against all Q queries (int32 compares on the VPU, 8×128
  lanes); per-query partial counts accumulate in a VMEM scratch that persists
  across the grid, written out on the last step. HBM traffic is exactly one
  read of the shard per query *batch* (not per query).
- :func:`z2_encode` / :func:`z3_encode` — the ingest hot loop
  (``curve/Z3SFC.scala:32``): Morton bit-interleave as magic-mask spreads in
  emulated 64-bit (two uint32 words), elementwise over lanes.

All kernels take ``interpret=`` so the same code runs on the CPU test mesh
(``tests/conftest.py``) and compiled on real TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from geomesa_tpu.ops.refine import MAX_BOXES, MAX_TIMES

LANES = 128


# ---------------------------------------------------------------------------
# batched count scan
# ---------------------------------------------------------------------------


def _tile_mask(nfo_ref, boxes_ref, times_ref, x_ref, y_ref, b_ref, o_ref,
               i, block_rows: int):
    """Score one (block_rows, 128) row tile against all queries.

    Returns ``(mask (Q, BR, L) bool, gpos (BR, L) int32 GLOBAL row
    positions)`` — the predicate evaluation shared by the count-only and
    the fused count+hits kernels (one definition; the two outputs must
    never drift)."""
    x = x_ref[:][None]  # (1, BR, L)
    y = y_ref[:][None]
    bb = b_ref[:][None]
    oo = o_ref[:][None]

    base = nfo_ref[0, 0]
    true_n = nfo_ref[0, 1]
    local_n = nfo_ref[0, 2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
    # columns are reshaped row-major (N/128, 128): element (r, c) = row r*128+c
    lpos = (i * block_rows + rows) * LANES + cols
    # mask tile-padding rows (lpos >= local_n) AND global-tail padding rows
    # (base + lpos >= true_n) — tile pads on interior shards would otherwise
    # alias into the next shard's global row range
    valid = ((lpos < local_n) & (base + lpos < true_n))[None]  # (1, BR, L)

    q = boxes_ref.shape[0]
    # slot counts come from the packed shapes (compile-time): single-box /
    # single-window batches pay for exactly one slot, not MAX_BOXES/MAX_TIMES
    in_box = jnp.zeros((q, block_rows, LANES), dtype=jnp.bool_)
    for k in range(boxes_ref.shape[1] // 4):
        xlo = boxes_ref[:, 4 * k + 0][:, None, None]
        xhi = boxes_ref[:, 4 * k + 1][:, None, None]
        ylo = boxes_ref[:, 4 * k + 2][:, None, None]
        yhi = boxes_ref[:, 4 * k + 3][:, None, None]
        in_box |= (x >= xlo) & (x <= xhi) & (y >= ylo) & (y <= yhi)

    in_time = jnp.zeros((q, block_rows, LANES), dtype=jnp.bool_)
    for k in range(times_ref.shape[1] // 4):
        blo = times_ref[:, 4 * k + 0][:, None, None]
        olo = times_ref[:, 4 * k + 1][:, None, None]
        bhi = times_ref[:, 4 * k + 2][:, None, None]
        ohi = times_ref[:, 4 * k + 3][:, None, None]
        after = (bb > blo) | ((bb == blo) & (oo >= olo))
        before = (bb < bhi) | ((bb == bhi) & (oo <= ohi))
        in_time |= after & before

    return in_box & in_time & valid, base + lpos


def _count_kernel(nfo_ref, boxes_ref, times_ref, x_ref, y_ref, b_ref, o_ref,
                  out_ref, acc_ref, *, block_rows: int):
    """One grid step: score a (block_rows, 128) row tile against all queries."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    mask, _ = _tile_mask(nfo_ref, boxes_ref, times_ref, x_ref, y_ref,
                         b_ref, o_ref, i, block_rows)
    m = mask.astype(jnp.int32)
    # reduce over sublanes only — a (Q, LANES) per-lane partial keeps every
    # vector 2D (Mosaic layout inference rejects narrow reshapes); the final
    # 128-lane fold happens host-side. explicit dtype: global x64 mode must
    # not promote the reduction to i64.
    acc_ref[:] = acc_ref[:] + jnp.sum(m, axis=1, dtype=jnp.int32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _count_hits_kernel(nfo_ref, boxes_ref, times_ref, x_ref, y_ref, b_ref,
                       o_ref, out_cnt_ref, out_pos_ref, acc_cnt_ref,
                       acc_pos_ref, *, block_rows: int):
    """Fused count + hit-position grid step (the subscription-matrix scan):
    per-lane count partials AND the most recent matched GLOBAL row position
    per lane (-1 = no match in that lane), accumulated across the grid in
    VMEM — one HBM pass serves both outputs."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_cnt_ref[:] = jnp.zeros_like(acc_cnt_ref)
        acc_pos_ref[:] = jnp.full_like(acc_pos_ref, -1)

    mask, gpos = _tile_mask(nfo_ref, boxes_ref, times_ref, x_ref, y_ref,
                            b_ref, o_ref, i, block_rows)
    acc_cnt_ref[:] = acc_cnt_ref[:] + jnp.sum(
        mask.astype(jnp.int32), axis=1, dtype=jnp.int32
    )
    # per-lane max over sublanes: rows are laid out row-major, so a larger
    # gpos IS a more recent row — the lane scoreboard keeps the newest
    # match per 128-row residue class without any sort/scatter
    posq = jnp.where(mask, gpos[None], -1)
    acc_pos_ref[:] = jnp.maximum(acc_pos_ref[:], jnp.max(posq, axis=1))

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_cnt_ref[:] = acc_cnt_ref[:]
        out_pos_ref[:] = acc_pos_ref[:]


@partial(jax.jit, static_argnames=("interpret", "block_rows"))
def batched_count(x, y, bins, offs, base, true_n, boxes, times, *,
                  interpret: bool = False, block_rows: int = 32):
    """Q bbox+time count queries over one shard slice, one HBM pass.

    Args:
      x, y, bins, offs: (N,) int32 sorted columns (device-resident slice).
      base: () int32 — global row offset of this slice (shard id × slice len).
      true_n: () int32 — global unpadded row count (validity bound).
      boxes: (Q, B, 4) int32 [xlo, xhi, ylo, yhi] inclusive, any slot
        count B (a compile-time shape: each slot is one unrolled test);
        padded slots made always-false by
        :func:`geomesa_tpu.ops.refine.pack_boxes`.
      times: (Q, T, 4) int32 [bin_lo, off_lo, bin_hi, off_hi], any T.

    Returns:
      (Q,) int32 per-query match counts for this slice.
    """
    n = x.shape[0]
    q = boxes.shape[0]
    tile = block_rows * LANES
    padded = ((n + tile - 1) // tile) * tile
    if padded != n:
        pad = padded - n
        x = jnp.pad(x, (0, pad))
        y = jnp.pad(y, (0, pad))
        bins = jnp.pad(bins, (0, pad))
        offs = jnp.pad(offs, (0, pad))
    shape2 = (padded // LANES, LANES)
    x2 = x.reshape(shape2)
    y2 = y.reshape(shape2)
    b2 = bins.reshape(shape2)
    o2 = offs.reshape(shape2)

    nfo = jnp.stack([jnp.asarray(base, jnp.int32),
                     jnp.asarray(true_n, jnp.int32),
                     jnp.asarray(n, jnp.int32)]).reshape(1, 3)
    nb4 = boxes.shape[1] * 4
    nt4 = times.shape[1] * 4
    boxes2 = boxes.reshape(q, nb4)
    times2 = times.reshape(q, nt4)

    grid = padded // tile
    col_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    # x64 off while tracing the kernel: Mosaic rejects the i64 index-map /
    # iota constants the global x64 mode would otherwise produce
    with enable_x64(False):
        counts = pl.pallas_call(
            partial(_count_kernel, block_rows=block_rows),
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, 3), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((q, nb4), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((q, nt4), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                col_spec, col_spec, col_spec, col_spec,
            ],
            out_specs=pl.BlockSpec((q, LANES), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((q, LANES), jnp.int32),
            scratch_shapes=[pltpu.VMEM((q, LANES), jnp.int32)],
            interpret=interpret,
        )(nfo, boxes2, times2, x2, y2, b2, o2)
    return counts.sum(axis=1, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("interpret", "block_rows"))
def batched_count_hits(x, y, bins, offs, base, true_n, boxes, times, *,
                       interpret: bool = False, block_rows: int = 32):
    """Q bbox+time count queries PLUS hit positions, one HBM pass.

    The subscription-matrix scan variant of :func:`batched_count`: same
    inputs and predicate semantics, but each grid step also keeps, per
    query and per 128-row lane, the most recent matched GLOBAL row
    position in a VMEM scoreboard — so counting and row retrieval for all
    Q standing queries cost exactly one pass over the chunk.

    Returns:
      counts: (Q,) int32 per-query match counts for this slice.
      lane_pos: (Q, 128) int32 — newest matched global row position per
        lane (-1 = that lane never matched). Callers ``top_k`` the lanes
        for the newest-match sample; counts stay exact regardless.
    """
    n = x.shape[0]
    q = boxes.shape[0]
    tile = block_rows * LANES
    padded = ((n + tile - 1) // tile) * tile
    if padded != n:
        pad = padded - n
        x = jnp.pad(x, (0, pad))
        y = jnp.pad(y, (0, pad))
        bins = jnp.pad(bins, (0, pad))
        offs = jnp.pad(offs, (0, pad))
    shape2 = (padded // LANES, LANES)
    x2 = x.reshape(shape2)
    y2 = y.reshape(shape2)
    b2 = bins.reshape(shape2)
    o2 = offs.reshape(shape2)

    nfo = jnp.stack([jnp.asarray(base, jnp.int32),
                     jnp.asarray(true_n, jnp.int32),
                     jnp.asarray(n, jnp.int32)]).reshape(1, 3)
    nb4 = boxes.shape[1] * 4
    nt4 = times.shape[1] * 4
    boxes2 = boxes.reshape(q, nb4)
    times2 = times.reshape(q, nt4)

    grid = padded // tile
    col_spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec((q, LANES), lambda i: (0, 0),
                            memory_space=pltpu.VMEM)
    with enable_x64(False):
        counts, lane_pos = pl.pallas_call(
            partial(_count_hits_kernel, block_rows=block_rows),
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((1, 3), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((q, nb4), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((q, nt4), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                col_spec, col_spec, col_spec, col_spec,
            ],
            out_specs=[out_spec, out_spec],
            out_shape=[
                jax.ShapeDtypeStruct((q, LANES), jnp.int32),
                jax.ShapeDtypeStruct((q, LANES), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM((q, LANES), jnp.int32),
                            pltpu.VMEM((q, LANES), jnp.int32)],
            interpret=interpret,
        )(nfo, boxes2, times2, x2, y2, b2, o2)
    return counts.sum(axis=1, dtype=jnp.int32), lane_pos


# ---------------------------------------------------------------------------
# Morton interleave (emulated 64-bit: two uint32 words)
# ---------------------------------------------------------------------------

# 3D spread masks (21 -> 63 bits), as (hi, lo) uint32 word pairs; mirrors
# geomesa_tpu.curve.zorder._M3 (the sfcurve-replacement magic numbers).
_M3_WORDS = [
    (0x00000000, 0x001FFFFF),
    (0x001F0000, 0x0000FFFF),
    (0x001F0000, 0xFF0000FF),
    (0x100F00F0, 0x0F00F00F),
    (0x10C30C30, 0xC30C30C3),
    (0x12492492, 0x49249249),
]
_M3_SHIFTS = [32, 16, 8, 4, 2]

# 2D spread masks (31 -> 62 bits)
_M2_WORDS = [
    (0x00000000, 0xFFFFFFFF),
    (0x0000FFFF, 0x0000FFFF),
    (0x00FF00FF, 0x00FF00FF),
    (0x0F0F0F0F, 0x0F0F0F0F),
    (0x33333333, 0x33333333),
    (0x55555555, 0x55555555),
]
_M2_SHIFTS = [16, 8, 4, 2, 1]


def _shl64(hi, lo, s: int):
    """(hi, lo) uint32 words << s, 0 < s <= 32."""
    if s == 32:
        return lo, jnp.zeros_like(lo)
    u = jnp.uint32
    return (hi << u(s)) | (lo >> u(32 - s)), lo << u(s)


def _spread_words(v, words, shifts):
    """Generic spread: v (uint32) -> 64-bit (hi, lo) with zero-bit gaps."""
    u = jnp.uint32
    hi = jnp.zeros_like(v)
    lo = v & u(words[0][1])
    hi = hi & u(words[0][0])
    for s, (mh, ml) in zip(shifts, words[1:]):
        sh, sl = _shl64(hi, lo, s)
        hi = (hi | sh) & u(mh)
        lo = (lo | sl) & u(ml)
    return hi, lo


def _or64(a, b):
    return a[0] | b[0], a[1] | b[1]


def _z3_kernel(x_ref, y_ref, t_ref, hi_ref, lo_ref):
    sx = _spread_words(x_ref[:], _M3_WORDS, _M3_SHIFTS)
    sy = _spread_words(y_ref[:], _M3_WORDS, _M3_SHIFTS)
    st = _spread_words(t_ref[:], _M3_WORDS, _M3_SHIFTS)
    hi, lo = _or64(_or64(sx, _shl64(*sy, 1)), _shl64(*st, 2))
    hi_ref[:] = hi
    lo_ref[:] = lo


def _z2_kernel(x_ref, y_ref, hi_ref, lo_ref):
    sx = _spread_words(x_ref[:], _M2_WORDS, _M2_SHIFTS)
    sy = _spread_words(y_ref[:], _M2_WORDS, _M2_SHIFTS)
    hi, lo = _or64(sx, _shl64(*sy, 1))
    hi_ref[:] = hi
    lo_ref[:] = lo


def _elementwise_call(kernel, arrs, n_out, interpret, block_rows=256):
    """Run an elementwise kernel over 1D uint32 arrays, tiled (BR, 128)."""
    n = arrs[0].shape[0]
    tile = block_rows * LANES
    padded = ((n + tile - 1) // tile) * tile
    arrs = [jnp.pad(a, (0, padded - n)) if padded != n else a for a in arrs]
    shape2 = (padded // LANES, LANES)
    arrs2 = [a.reshape(shape2) for a in arrs]
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    with enable_x64(False):
        outs = pl.pallas_call(
            kernel,
            grid=(padded // tile,),
            in_specs=[spec] * len(arrs2),
            out_specs=[spec] * n_out,
            out_shape=[jax.ShapeDtypeStruct(shape2, jnp.uint32)] * n_out,
            interpret=interpret,
        )(*arrs2)
    return [o.reshape(padded)[:n] for o in outs]


@partial(jax.jit, static_argnames=("interpret",))
def z3_encode(x, y, t, *, interpret: bool = False):
    """Morton-interleave three <=21-bit uint32 arrays -> (hi, lo) uint32 words.

    ``z = hi << 32 | lo`` matches :func:`geomesa_tpu.curve.zorder.encode3`.
    """
    x = x.astype(jnp.uint32)
    y = y.astype(jnp.uint32)
    t = t.astype(jnp.uint32)
    hi, lo = _elementwise_call(_z3_kernel, [x, y, t], 2, interpret)
    return hi, lo


@partial(jax.jit, static_argnames=("interpret",))
def z2_encode(x, y, *, interpret: bool = False):
    """Morton-interleave two <=31-bit uint32 arrays -> (hi, lo) uint32 words."""
    x = x.astype(jnp.uint32)
    y = y.astype(jnp.uint32)
    hi, lo = _elementwise_call(_z2_kernel, [x, y], 2, interpret)
    return hi, lo
