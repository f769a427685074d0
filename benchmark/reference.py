"""Arithmetic that the queries' plain references share: numpy only,
nothing of the program.

`precision` is what a reference computes in. "float64" is the
reference proper. A lower one is a CONTROL of "How `correct` is
decided": the reference put in the program's place, every value and
every product rounded to that precision, which the comparison has to
refuse. "float32" accumulates in float32; "bfloat16" rounds values and
products to bfloat16 and accumulates in float32, as a matrix unit fed
bfloat16 does.
"""

import numpy as np

#: Below this many groups a mask per group is cheaper than a sort.
_MASK_GROUPS = 64


class Precision:
    def __init__(self, name: str = "float64"):
        if name not in ("float64", "float32", "bfloat16"):
            raise ValueError(f"unknown precision {name!r}")
        self.name = name
        self.acc = np.float64 if name == "float64" else np.float32

    def cast(self, x) -> np.ndarray:
        """`x` rounded to this precision (bfloat16: held as float32)."""
        x = np.asarray(x).astype(self.acc, copy=False)
        if self.name != "bfloat16":
            return x
        bits = np.ascontiguousarray(x).view(np.uint32)
        # round to nearest, ties to even, at the 16th bit
        bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
        return bits.view(np.float32)

    def mul(self, a, b):
        return self.cast(self.cast(a) * self.cast(b))

    def add(self, a, b):
        return self.cast(self.cast(a) + self.cast(b))

    def sub(self, a, b):
        return self.cast(self.cast(a) - self.cast(b))


def group_sums(values: np.ndarray, gid: np.ndarray, groups: int,
               precision: Precision) -> np.ndarray:
    """Sum of `values` per group id 0..groups-1; rows with another id
    (filtered out) are left out. Accumulates in `precision.acc`."""
    dtype = np.dtype(precision.acc)
    if dtype == np.float64:
        keep = gid < groups
        return np.bincount(gid[keep], weights=values[keep],
                           minlength=groups)[:groups]
    values = precision.cast(values)
    out = np.zeros(groups, dtype=dtype)
    if groups <= _MASK_GROUPS:
        for g in range(groups):
            out[g] = values[gid == g].sum(dtype=dtype)
        return out.astype(np.float64)
    order = np.argsort(gid, kind="stable")
    sorted_gid = gid[order]
    starts = np.searchsorted(sorted_gid, np.arange(groups))
    ends = np.searchsorted(sorted_gid, np.arange(groups), side="right")
    sorted_vals = values[order]
    for g in np.flatnonzero(ends > starts):
        out[g] = sorted_vals[starts[g]:ends[g]].sum(dtype=dtype)
    return out.astype(np.float64)


def group_counts(gid: np.ndarray, groups: int) -> np.ndarray:
    return np.bincount(gid[gid < groups], minlength=groups)[:groups]


def column(table, name: str) -> np.ndarray:
    """A null-free arrow column as numpy (dictionary: its codes)."""
    col = table.column(name).combine_chunks()
    if hasattr(col, "indices"):
        col = col.indices
    return col.to_numpy(zero_copy_only=False)
