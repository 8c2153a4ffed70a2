"""Pallas TPU kernels: FM move-gain assembly.

Second hot spot of the partitioner: turning per-edge state into per-vertex
k-way gains.  Two stages:

  1. edge terms (cheap, done in jnp inside core/metrics.py): from
     Phi[M, k] compute ``becomes_internal[M, k]`` and ``was_internal[M]``.
  2. **this kernel**: for each vertex, gather + sum the rows of its
     incident edges — a fused gather-reduce over the dual CSR, re-blocked
     as a padded incidence matrix ``incident[N, D]`` (pad = -1).

The gather runs on the MXU as a one-hot matmul.  For a vertex tile of
``bn`` vertices and an edge window of ``bm`` table rows the kernel
builds the incidence-count matrix ``counts[bm, bn]`` (how often edge e
appears among vertex v's D incident slots; pad slots match no row) one
incident slot at a time, then

    gains^T[k, bn]  +=  bi^T[k, bm] @ counts  -  wi[1, bm] @ counts

Every operand is 2-D with the vertex axis on the lanes, which is why
the tables and the result travel transposed (``[k, M]`` / ``[k, N]``)
and the incidence as ``[D, N]``: the TPU compiler tiles in (8, 128)
blocks and has no 3-D row gather.

The kernel (``gain_stream_pallas``) streams the per-edge tables over a
second grid axis: tile ``t`` sees only rows ``[t*block_m,
(t+1)*block_m)`` of the tables and accumulates its partial gains into
the output tile, which stays resident in VMEM across all edge-table
tiles of a vertex tile (the TPU grid is sequential, so revisiting the
same output block is the idiomatic scratch accumulator).  No [M, k]
table and no [P, k] per-pin tensor is ever materialised whole, so any
(M, k) fits.

It launches population-batched: a leading ``alpha`` grid axis, squeezed
out of the blocks, shares the incidence tile across members (same
hypergraph) while each member brings its own ``becomes_internal`` /
``was_internal`` tables.  The single-member entry point is the same
launch with ``alpha = 1``, so a member's slice is bit-equal to its own
single-member launch by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (GAIN_BLOCK_N, pad_rows as _pad_rows,
                     stream_block_m as _stream_bm)


def _gain_stream_kernel(inct_ref, bit_ref, wi_ref, out_ref):
    """Partial gains^T [k, bn] of edge-table tile ``t``: bit [k, bm],
    wi [1, bm], accumulated over the tiles into the output block."""
    t = pl.program_id(2)                          # edge-table tile index
    depth, bn = inct_ref.shape
    block_m = bit_ref.shape[1]
    rows = (jax.lax.broadcasted_iota(jnp.int32, (block_m, bn), 0)
            + t * block_m)

    def slot(d, counts):
        return counts + (inct_ref[pl.ds(d, 1), :] == rows).astype(jnp.float32)

    counts = jax.lax.fori_loop(0, depth, slot,
                               jnp.zeros(rows.shape, jnp.float32))
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    partial = dot(bit_ref[...], counts) - dot(wi_ref[...], counts)

    # the output tile doubles as the VMEM scratch accumulator: its index
    # map ignores t, so the same block stays resident across the whole
    # edge-table sweep (sequential TPU grid makes the += race-free)
    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


def _transposed_operands(incident, becomes_internal, was_internal,
                         block_n, block_m):
    """Pad and transpose to the kernels' lane-major layouts:
    incident^T [D, N_pad], bi^T [alpha, k, M_pad], wi [alpha, 1, M_pad]."""
    inct = _pad_rows(incident, block_n, -1).T
    m_tail = (-becomes_internal.shape[1]) % block_m
    bit = jnp.pad(becomes_internal, ((0, 0), (0, m_tail), (0, 0)))
    wi = jnp.pad(was_internal, ((0, 0), (0, m_tail)))
    return inct, jnp.swapaxes(bit, 1, 2), wi[:, None, :]


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def gain_stream_batch_pallas(incident: jnp.ndarray,
                             becomes_internal: jnp.ndarray,
                             was_internal: jnp.ndarray,
                             block_n: int = GAIN_BLOCK_N,
                             block_m: int | None = None,
                             interpret: bool = True) -> jnp.ndarray:
    """Population-batched streaming gain assembly.

    incident: [N, D] int32 (shared, pad = -1);
    becomes_internal: [alpha, M, k]; was_internal: [alpha, M];
    returns gains [alpha, N, k].  Grid ``(alpha, N//bn, M//bm)`` — the
    shared incidence tile ignores the population index, each member
    streams its own edge-table tiles, and the per-(member, vertex-tile)
    output block accumulates across the edge sweep.  Block sizes default
    to the largest that keep the [bm, bn] count tile and the [k, bm]
    table tile within ``common.GAIN_STREAM_TILE_BYTES``.
    """
    n, d = incident.shape
    alpha, m, k = becomes_internal.shape
    assert was_internal.shape == (alpha, m)
    if block_m is None:
        block_m = _stream_bm(k)
    inct, bit, wi = _transposed_operands(incident, becomes_internal,
                                         was_internal, block_n, block_m)
    n_pad, m_pad = inct.shape[1], bit.shape[2]
    out = pl.pallas_call(
        _gain_stream_kernel,
        grid=(alpha, n_pad // block_n, m_pad // block_m),  # edge axis last
        in_specs=[
            pl.BlockSpec((d, block_n), lambda a, i, t: (0, i)),
            pl.BlockSpec((None, k, block_m), lambda a, i, t: (a, 0, t)),
            pl.BlockSpec((None, 1, block_m), lambda a, i, t: (a, 0, t)),
        ],
        out_specs=pl.BlockSpec((None, k, block_n), lambda a, i, t: (a, 0, i)),
        out_shape=jax.ShapeDtypeStruct((alpha, k, n_pad), jnp.float32),
        interpret=interpret,
    )(inct, bit, wi)
    return jnp.swapaxes(out, 1, 2)[:, :n]


def gain_stream_pallas(incident: jnp.ndarray, becomes_internal: jnp.ndarray,
                       was_internal: jnp.ndarray, block_n: int = GAIN_BLOCK_N,
                       block_m: int | None = None, interpret: bool = True
                       ) -> jnp.ndarray:
    """gains[N, k] = sum_d bi[incident[v, d]] - sum_d wi[incident[v, d]].

    ``incident`` rows need NOT be a multiple of ``block_n``: the kernel
    pads internally (pad rows gather nothing) and slices the result.
    The ``alpha = 1`` batch launch."""
    return gain_stream_batch_pallas(incident, becomes_internal[None],
                                    was_internal[None], block_n=block_n,
                                    block_m=block_m, interpret=interpret)[0]
