import numpy as np

from benchmark.kernel_bytes import batched_count_bytes


def test_batched_count_bytes_from_shapes():
    cols = {k: np.zeros(4096, np.int32) for k in ("x", "y", "bins", "offs")}
    cols["ignored"] = np.zeros(10**6, np.int64)
    assert batched_count_bytes(cols) == 4 * 4096 * 4


def test_batched_count_bytes_follow_dtype():
    cols = {"x": np.zeros(8, np.int32), "y": np.zeros(8, np.int32),
            "bins": np.zeros(8, np.int16), "offs": np.zeros(8, np.int32)}
    assert batched_count_bytes(cols) == 8 * (4 + 4 + 2 + 4)
