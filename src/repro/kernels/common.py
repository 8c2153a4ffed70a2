"""Shared helpers + sizing constants for the Pallas kernels.

The constants below are the single source of truth for every "does this
fit on-chip?" gate in :mod:`repro.kernels.ops` (they used to be magic
numbers scattered over the call sites).
"""
from __future__ import annotations

import jax.numpy as jnp

#: Per-core VMEM working-set budget the kernels size themselves against.
#: Current TPU cores expose ~16 MiB of VMEM; a kernel invocation should
#: stay well under it so the pipelined (double-buffered) operand tiles,
#: the output tile and the scratch accumulator all fit at once.
VMEM_BUDGET_BYTES = 16 * 2**20

#: Hard cap on ``k`` for the single-word kernels: the
#: connectivity/cutsize kernels pack "edge touches block j" into one
#: uint32 lane bitmask, so k is capped by the 32-bit VPU word.  Beyond
#: it, connectivity falls back to the XLA segment-sum; the XLA gain
#: assembly switches from the [P, k] segment-sum to the compact path.
KERNEL_MAX_K = 32

#: Budget for one streamed [k, block_m] table tile of the
#: ``gain_stream_*`` kernel.  Block sizes are derived from it at trace
#: time.
GAIN_STREAM_TILE_BYTES = VMEM_BUDGET_BYTES // 8

#: Vertex-tile lanes of the gain kernel (a multiple of the 128-lane
#: vreg width).
GAIN_BLOCK_N = 256

#: Upper bound on the edge rows per incidence-count tile of the gain
#: kernel: the [GAIN_WINDOW_M, GAIN_BLOCK_N] f32 count matrix (512 KiB)
#: is the largest tensor it materialises; the gather runs as a matmul of
#: the table tile against it on the MXU.
GAIN_WINDOW_M = 512


#: Budget for one tile pair of the rating scatter kernel
#: (``kernels/rating.py``): the [block_c, block_s] one-hot membership
#: matrix is the largest tensor it materialises (the segment-sum runs as
#: a matmul against it on the MXU).
RATING_TILE_BYTES = VMEM_BUDGET_BYTES // 8

#: Routing bound for the rating kernel.  Its grid is dense over
#: (segment tiles x candidate tiles) — quadratic in the candidate count,
#: so it is the coarse/mid-level tool.
#: Above this candidate count the dispatcher falls back to the XLA
#: segment-sum (sorted-scatter, linear).  32K candidates with the
#: default 512x1024 tiles is ~2K grid steps.
RATING_KERNEL_MAX_C = 32768


def pad_rows(x: jnp.ndarray, mult: int, fill) -> jnp.ndarray:
    """Pad axis 0 of ``x`` up to a multiple of ``mult`` with ``fill``.

    Lets every kernel accept row counts that are not multiples of its
    block size: pad rows are inert (pin/edge id -1 or weight 0) and the
    caller slices them off the result.
    """
    r = x.shape[0]
    r_pad = ((r + mult - 1) // mult) * mult
    if r_pad == r:
        return x
    widths = [(0, r_pad - r)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _pow2_floor(x: int, lo: int, hi: int) -> int:
    """Largest power of two in [lo, hi] that is <= x (clamped)."""
    x = max(int(x), lo)
    p = 1 << (x.bit_length() - 1)
    return int(min(max(p, lo), hi))


def stream_block_m(k: int) -> int:
    """Edge-table tile rows for the streaming gain kernels: the
    [k, bm] table tile must fit ``GAIN_STREAM_TILE_BYTES``, and bm is a
    lane multiple no larger than the count window."""
    return _pow2_floor(GAIN_STREAM_TILE_BYTES // max(k * 4, 1), 128,
                       GAIN_WINDOW_M)


def rating_blocks() -> tuple:
    """(block_s, block_c) for the rating scatter kernel: segment-tile
    lanes x candidate-tile rows, sized so the [block_c, block_s] one-hot
    matrix fits ``RATING_TILE_BYTES``."""
    bs = 512
    bc = _pow2_floor(RATING_TILE_BYTES // (bs * 4), 128, 1024)
    return bs, bc
