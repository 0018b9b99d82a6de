"""Scoped VMEM limit of the SpGEMM ``pallas_call``s.

Mosaic gives a kernel 16 MiB of scoped VMEM unless the call asks for more.
The v5e compiler accepts a limit up to the chip's whole 128 MiB: compiling
for a described ``v5e:2x2`` chip, double-buffered blocks totalling 128 MiB
compile under ``vmem_limit_bytes=128 MiB``, while 130 MiB is refused as
exceeding the VMEM capacity. So a kernel sized by the planner to more than
the default must say so in its call.

Each SpGEMM kernel counts its resident footprint from its own block shapes —
the staged pieces its byte model in ``repro.core.planner`` prices — with the
second buffer the Pallas pipeline keeps of every blocked operand, and asks
:func:`compiler_params` for a limit that covers it. A footprint that cannot
fit the chip raises here, before Mosaic is reached, naming the bytes.
"""

from __future__ import annotations

import numpy as np
from jax.experimental.pallas import tpu as pltpu

DEFAULT_SCOPED_VMEM_BYTES = 16 << 20
VMEM_CAPACITY_BYTES = 128 << 20
# Mosaic's own internal scratch (semaphores, spills of the loop carries)
HEADROOM_BYTES = 2 << 20


def tiled_nbytes(shape, dtype) -> int:
    """Bytes of one VMEM array: the last two dimensions pad to the (8, 128)
    tile of 32-bit values (sublanes scale with the packing of narrower
    types), so a narrow block costs a whole tile row."""
    itemsize = np.dtype(dtype).itemsize
    dims = [int(d) for d in shape] or [1]
    sublanes = 8 * max(1, 4 // itemsize)
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        dims[-2] = -(-dims[-2] // sublanes) * sublanes
    return int(np.prod(dims, dtype=np.int64)) * itemsize


def resident_bytes(blocked=(), scratch=(), workspace: float = 0.0) -> int:
    """Peak VMEM of one kernel: two buffers of every blocked operand
    (``(shape, dtype)`` pairs), the explicit scratch once, and the body's
    largest temporaries (``workspace`` bytes)."""
    return (2 * sum(tiled_nbytes(s, d) for s, d in blocked)
            + sum(tiled_nbytes(s, d) for s, d in scratch)
            + int(workspace))


def need_bytes(blocked=(), scratch=(), workspace: float = 0.0) -> int:
    """The scoped VMEM limit a kernel asks for: its resident footprint plus
    Mosaic's headroom."""
    return resident_bytes(blocked, scratch, workspace) + HEADROOM_BYTES


def compiler_params(*, blocked=(), scratch=(), workspace: float = 0.0,
                    **kwargs) -> pltpu.CompilerParams:
    """``pltpu.CompilerParams`` whose ``vmem_limit_bytes`` covers the
    kernel's resident footprint (see :func:`resident_bytes`), never below
    the compiler's default. Extra ``kwargs`` pass through
    (``dimension_semantics``)."""
    need = need_bytes(blocked, scratch, workspace)
    if need > VMEM_CAPACITY_BYTES:
        raise ValueError(
            f"kernel needs {need} B of VMEM, more than the chip's "
            f"{VMEM_CAPACITY_BYTES} B: plan smaller strips or chunks")
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(DEFAULT_SCOPED_VMEM_BYTES, need), **kwargs)
