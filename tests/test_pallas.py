"""Pallas kernel parity tests (interpret mode on the CPU test mesh).

The kernels are the TPU re-materialization of the reference's server-side hot
loops (``Z3Filter.inBounds`` int-domain compares, ``sfcurve`` Morton spreads —
SURVEY.md §2.9). Interpret mode runs the same kernel code the TPU compiles;
parity is asserted against independent numpy referees.
"""

import numpy as np
import pytest

import geomesa_tpu  # noqa: F401
from geomesa_tpu.curve import zorder
from geomesa_tpu.ops.pallas_kernels import batched_count, z2_encode, z3_encode
from geomesa_tpu.ops.refine import (
    MAX_BOXES,
    MAX_TIMES,
    pack_boxes,
    pack_times,
    trim_slots,
    unsat_rows,
)


def _referee(x, y, b, o, boxes, times):
    q = len(boxes)
    out = np.zeros(q, np.int64)
    for qi in range(q):
        inb = np.zeros(len(x), bool)
        for k in range(MAX_BOXES):
            bx = boxes[qi, k]
            inb |= (x >= bx[0]) & (x <= bx[1]) & (y >= bx[2]) & (y <= bx[3])
        int_ = np.zeros(len(x), bool)
        for k in range(MAX_TIMES):
            tt = times[qi, k]
            after = (b > tt[0]) | ((b == tt[0]) & (o >= tt[1]))
            before = (b < tt[2]) | ((b == tt[2]) & (o <= tt[3]))
            int_ |= after & before
        out[qi] = (inb & int_).sum()
    return out


@pytest.fixture(scope="module")
def cols(rng):
    n = 4000
    return (
        rng.integers(0, 2**31 - 1, n).astype(np.int32),
        rng.integers(0, 2**31 - 1, n).astype(np.int32),
        rng.integers(0, 50, n).astype(np.int32),
        rng.integers(0, 86_400_000, n).astype(np.int32),
    )


def _payload(rng, q):
    boxes, times = [], []
    for _ in range(q):
        xs = np.sort(rng.integers(0, 2**31 - 1, 2).astype(np.int32))
        ys = np.sort(rng.integers(0, 2**31 - 1, 2).astype(np.int32))
        boxes.append(pack_boxes(np.array([[xs[0], xs[1], ys[0], ys[1]]], np.int32)))
        blo, bhi = np.sort(rng.integers(0, 50, 2).astype(np.int32))
        times.append(
            pack_times(np.array([[blo, 0, bhi, 50_000_000]], np.int32))
        )
    return np.stack(boxes), np.stack(times)


class TestBatchedCount:
    def test_parity(self, rng, cols):
        x, y, b, o = cols
        boxes, times = _payload(rng, 5)
        got = np.asarray(
            batched_count(x, y, b, o, 0, len(x), boxes, times, interpret=True)
        )
        ref = _referee(x, y, b, o, boxes, times)
        assert (got == ref).all()

    def test_base_offset_and_padding_masked(self, rng, cols):
        """Interior-shard tile padding must not alias the next shard's rows."""
        x, y, b, o = cols
        boxes = np.stack([pack_boxes(None)])  # whole world
        times = np.stack([pack_times(None)])
        # slice is 4000 rows at base 0 of a 3500-row "global" store: rows
        # >= 3500 are global-tail padding; tile pads (4000->4096) are local
        got = np.asarray(
            batched_count(x, y, b, o, 0, 3500, boxes, times, interpret=True)
        )
        assert got[0] == 3500
        # interior shard: base 4000, global n huge — every local row counts,
        # tile padding (rows 4000..4095) must NOT
        got = np.asarray(
            batched_count(x, y, b, o, 4000, 10**9, boxes, times, interpret=True)
        )
        assert got[0] == 4000

    def test_multi_slot_or_semantics(self, rng, cols):
        x, y, b, o = cols
        b1 = np.array([[0, 2**30, 0, 2**30], [2**30, 2**31 - 1, 0, 2**31 - 1]],
                      np.int32)
        boxes = np.stack([pack_boxes(b1)])
        times = np.stack([pack_times(np.array([[0, 0, 10, 0], [20, 0, 50, 10**8]],
                                              np.int32))])
        got = np.asarray(
            batched_count(x, y, b, o, 0, len(x), boxes, times, interpret=True)
        )
        ref = _referee(x, y, b, o, boxes, times)
        assert (got == ref).all()


def _slot_payload(rng, n_boxes, n_times, q=5):
    """Query 0 packs ``n_boxes`` boxes and ``n_times`` windows (None: no
    time constraint) at full width; the rest one box and one window."""
    boxes, times = [], []
    for k in range(q):
        nb, nt = (n_boxes, n_times) if k == 0 else (1, 1)
        bx = np.sort(rng.integers(0, 2**31 - 1, (nb, 2, 2)), axis=2)
        boxes.append(pack_boxes(bx.reshape(nb, 4).astype(np.int32)))
        if nt is None:
            times.append(pack_times(None))
            continue
        bins = np.sort(rng.integers(0, 50, (nt, 2)), axis=1)
        offs = rng.integers(0, 86_400_000, (nt, 2))
        times.append(pack_times(np.stack(
            [bins[:, 0], offs[:, 0], bins[:, 1], offs[:, 1]], axis=1
        ).astype(np.int32)))
    return np.stack(boxes), np.stack(times)


class TestTrimSlots:
    @pytest.mark.parametrize("n_boxes,n_times,want", [
        (1, 1, (1, 1)),
        (3, 5, (4, 8)),
        (9, 20, (1, 1)),  # both widened to one enveloping slot by pack_*
        (1, None, (1, 1)),
        (3, None, (4, 1)),
        (9, 5, (1, 8)),
    ])
    def test_trimmed_scan_matches_full(self, rng, cols, n_boxes, n_times,
                                       want):
        x, y, b, o = cols
        boxes, times = _slot_payload(rng, n_boxes, n_times)
        tb, tt, _ = trim_slots(boxes, times)
        assert (tb.shape[1], tt.shape[1]) == want
        full = np.asarray(
            batched_count(x, y, b, o, 0, len(x), boxes, times, interpret=True)
        )
        trimmed = np.asarray(
            batched_count(x, y, b, o, 0, len(x), tb, tt, interpret=True)
        )
        assert (trimmed == full).all()
        assert (full == _referee(x, y, b, o, boxes, times)).all()

    @pytest.mark.parametrize("overlap", [False, True])
    def test_live_counts_and_widths(self, overlap):
        one = np.array([[0, 9, 0, 9]], np.int32)
        three = np.array([[0, 1, 0, 1], [2, 3, 2, 3], [4, 5, 4, 5]], np.int32)
        boxes = np.stack([pack_boxes(one, overlap=overlap),
                          pack_boxes(three, overlap=overlap)])
        times = np.stack([pack_times(None),
                          pack_times(np.array([[0, 0, 1, 1]] * 2, np.int32))])
        tb, tt, live = trim_slots(boxes, times, overlap=overlap)
        assert tb.shape == (2, 4, 4) and tt.shape == (2, 2, 4)
        assert live == (1 + 3) + (1 + 2)
        # the kept columns are the leading ones, unchanged
        assert (tb == boxes[:, :4]).all() and (tt == times[:, :2]).all()

    def test_each_mode_drops_only_its_own_pad(self):
        """[1, 0, 1, 0] is the point-mode pad but NOT empty under interval
        overlap: an overlap batch keeps it as a live slot."""
        b = np.array([[0, 9, 0, 9], [1, 0, 1, 0]], np.int32)
        times = np.stack([pack_times(None)])
        for overlap, width in ((True, 2), (False, 1)):
            boxes = np.stack([pack_boxes(b, overlap=overlap)])
            tb = trim_slots(boxes, times, overlap=overlap)[0]
            assert tb.shape[1] == width, overlap

    def test_real_slot_after_a_pad_equal_slot_is_kept(self):
        """Trimming keeps everything up to the LAST live slot: a real slot
        that happens to equal the pad never hides one after it."""
        b = np.array([[0, 9, 0, 9], [1, 0, 1, 0], [3, 7, 3, 7]], np.int32)
        boxes = np.stack([pack_boxes(b)])
        times = np.stack([pack_times(None)])
        tb, _, live = trim_slots(boxes, times)
        assert tb.shape[1] == 4 and live == 3 + 1

    def test_all_pad_query_keeps_one_slot(self):
        """The unsatisfiable payload (every slot a pad) trims to one slot
        per axis and still matches nothing."""
        ub, ut = unsat_rows(MAX_BOXES, MAX_TIMES)
        tb, tt, live = trim_slots(ub[None], ut[None])
        assert tb.shape == (1, 1, 4) and tt.shape == (1, 1, 4) and live == 0


class TestZEncode:
    def test_z3_matches_zorder(self, rng):
        n = 3000
        xs = rng.integers(0, 2**21, n).astype(np.uint32)
        ys = rng.integers(0, 2**21, n).astype(np.uint32)
        ts = rng.integers(0, 2**21, n).astype(np.uint32)
        hi, lo = z3_encode(xs, ys, ts, interpret=True)
        z = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
            lo
        ).astype(np.uint64)
        assert (z == zorder.encode3(xs, ys, ts)).all()

    def test_z2_matches_zorder(self, rng):
        n = 3000
        xs = rng.integers(0, 2**31, n).astype(np.uint32)
        ys = rng.integers(0, 2**31, n).astype(np.uint32)
        hi, lo = z2_encode(xs, ys, interpret=True)
        z = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
            lo
        ).astype(np.uint64)
        assert (z == zorder.encode2(xs, ys)).all()

    def test_edge_values(self):
        xs = np.array([0, 1, 2**21 - 1], np.uint32)
        hi, lo = z3_encode(xs, xs, xs, interpret=True)
        z = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
            lo
        ).astype(np.uint64)
        assert (z == zorder.encode3(xs, xs, xs)).all()
        assert z[2] == np.uint64(0x7FFFFFFFFFFFFFFF)
