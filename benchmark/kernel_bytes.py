"""Bytes a kernel must move per call, from the shapes of its operands.

Kept with the benchmark so no later PR that claims a gain can change how
its roofline is counted.
"""

from __future__ import annotations

import numpy as np

BATCHED_COUNT_COLUMNS = ("x", "y", "bins", "offs")


def batched_count_bytes(columns: dict) -> int:
    """One ``batched_count`` call reads each resident column of the z3
    index once (the fused scan keeps every query of the batch in VMEM and
    makes one HBM pass); the query payload and the (Q, 128) output are a
    few KB and left out."""
    return sum(int(np.prod(columns[k].shape)) * np.dtype(columns[k].dtype).itemsize
               for k in BATCHED_COUNT_COLUMNS)
