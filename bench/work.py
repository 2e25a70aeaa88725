"""Work counts and device peaks: the yardstick's side of every roofline.

The count of a kernel's bytes does not depend on how the program stores
the matrix, so no change of format, layout or kernel can move it.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def spmv_min_bytes(nnz: int, nrows: int, ncols: int) -> int:
    """The least HBM traffic of one f32 ``y = A @ x``: every stored value
    read once, ``x`` read once, ``y`` written once. Indices are not
    counted: a matrix-free or structured kernel need not read any."""
    return 4 * (int(nnz) + int(nrows) + int(ncols))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
