"""Pallas TPU kernel: heavy-edge pair-rating aggregation.

Hot spot of the device coarsener (``core/dcoarsen``): after the
per-round candidate pairs are lexicographically sorted, duplicate pairs
(the same (u, v) rated by several incident edges) occupy a contiguous
run and carry a *sorted* segment id.  Their ratings

    r(u, v) = sum_e w_e / (|e| - 1)

must be segment-summed into one slot per distinct pair — a scatter over
up to ``max_stride * P_pad`` candidates every round.

The kernel tiles exactly like ``gain_stream_pallas``: the output
segment tile stays resident in VMEM across the whole candidate sweep
(the innermost grid axis, sequential on TPU, accumulates race-free with
``+=``) while (value, segment-id) tiles stream through.  Each tile's
partial sums are a matmul of the ``[1, block_c]`` value row against the
``[block_s, block_c]`` one-hot membership matrix (contracted on the
candidate axis) — the MXU does the scatter, no per-element stores.
Every operand is 2-D and lane-major, the layout the TPU compiler
tiles in (8, 128) blocks.  Because the segment ids are sorted, at most
``ceil(block_c / block_s) + 1`` candidate tiles overlap any output
tile; every other (i, t) pair short-circuits through ``pl.when``.

The grid itself is still dense over (segment tiles x candidate tiles)
— quadratic in the candidate count, which is fine only in the
coarse/mid rounds.  The
``kernels.ops.rating_path`` dispatcher bounds it at
``common.RATING_KERNEL_MAX_C`` candidates and routes the fine rounds
to the linear XLA segment-sum.

There is one launch shape, population-batched (DESIGN.md §10): the
leading ``alpha`` grid axis is squeezed out of the value and output
blocks, the mutation cohort shares one candidate structure (the
segment-id index map ignores the population index) and each flagged
member streams its own reweighted rating values.  The single-member
entry point is the same launch with ``alpha = 1``, so a member's row is
bit-equal to its own single-member launch by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import pad_rows as _pad_rows, rating_blocks as _rating_blocks


def _rating_scatter_kernel(seg_ref, val_ref, out_ref, *, block_s: int):
    i = pl.program_id(1)                       # output segment tile
    t = pl.program_id(2)                       # candidate tile (streamed)
    seg = seg_ref[...]                         # [1, bc] int32, pad -1
    local = seg - i * block_s
    valid = (seg >= 0) & (local >= 0) & (local < block_s)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # sorted ids: most tiles skip
    @pl.when(jnp.max(valid.astype(jnp.int32)) > 0)
    def _accumulate():
        rows = jax.lax.broadcasted_iota(jnp.int32,
                                        (block_s, seg.shape[1]), 0)
        onehot = (jnp.where(valid, local, -1) == rows
                  ).astype(jnp.float32)        # [bs, bc]
        val = jnp.where(valid, val_ref[...], 0.0)   # [1, bc]
        out_ref[...] += jax.lax.dot_general(
            val, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)      # [1, bs]


@functools.partial(jax.jit, static_argnames=("num_segments", "block_s",
                                             "block_c", "interpret"))
def rating_scatter_batch_pallas(vals: jnp.ndarray, segs: jnp.ndarray,
                                num_segments: int, block_s: int | None = None,
                                block_c: int | None = None,
                                interpret: bool = True) -> jnp.ndarray:
    """Population-batched sorted-segment sum for the mutation cohort.

    vals: [alpha, C] f32 per-member candidate ratings; segs: [C] int32
    ascending, SHARED by all members (one candidate structure, ids < 0
    dropped; their vals must be 0 in every row).  Returns
    [alpha, num_segments] f32.  Grid ``(alpha, s_tiles, c_tiles)``: the
    segment tile index map ignores the population index, so the same
    candidate tile serves every member while per-member value tiles
    stream through.
    """
    if block_s is None or block_c is None:
        dbs, dbc = _rating_blocks()
        block_s = block_s or dbs
        block_c = block_c or dbc
    alpha, c = vals.shape
    assert segs.shape == (c,)
    segs = _pad_rows(segs, block_c, -1)[None]              # [1, C_pad]
    vals = _pad_rows(vals.T, block_c, 0.0).T[:, None]      # [alpha, 1, C_pad]
    c_pad = segs.shape[1]
    s_pad = ((num_segments + block_s - 1) // block_s) * block_s
    grid = (alpha, s_pad // block_s, c_pad // block_c)
    out = pl.pallas_call(
        functools.partial(_rating_scatter_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c), lambda a, i, t: (0, t)),   # shared
            pl.BlockSpec((None, 1, block_c), lambda a, i, t: (a, 0, t)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_s), lambda a, i, t: (a, 0, i)),
        out_shape=jax.ShapeDtypeStruct((alpha, 1, s_pad), jnp.float32),
        interpret=interpret,
    )(segs, vals)
    return out[:, 0, :num_segments]


def rating_scatter_pallas(vals: jnp.ndarray, segs: jnp.ndarray,
                          num_segments: int, block_s: int | None = None,
                          block_c: int | None = None,
                          interpret: bool = True) -> jnp.ndarray:
    """Sorted-segment sum: out[s] = sum over candidates with segs == s.

    vals: [C] f32; segs: [C] int32 ascending (invalid/pad entries may
    carry any id — their vals must be 0; ids < 0 are ignored outright).
    Returns [num_segments] f32.  The ``alpha = 1`` batch launch.
    """
    return rating_scatter_batch_pallas(vals[None], segs, num_segments,
                                       block_s=block_s, block_c=block_c,
                                       interpret=interpret)[0]
