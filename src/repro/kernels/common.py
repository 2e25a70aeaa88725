"""What every Pallas SpMV kernel shares: the interpret decision and the
TPU vector-register geometry the block layouts are cut to."""
from __future__ import annotations

import jax

#: One f32 vector register is 8 sublanes x 128 lanes; blocks whose last two
#: dims are multiples of this (or the whole array dim) are what Mosaic tiles.
SUBLANES, LANES = 8, 128
VREG = SUBLANES * LANES


def interpret_mode(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument: ``None`` compiles the
    kernel natively on a TPU and runs the Pallas interpreter anywhere else
    (CPU tests); an explicit bool wins (compile rehearsals pass ``False``)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m
