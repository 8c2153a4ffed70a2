"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
used by the per-kernel allclose test sweeps).

Layouts match the kernels: hyperedges as a padded pin matrix
``pins[M, S]`` (pad = -1), partition ids ``part[N]``, ``k`` blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def connectivity_ref(pins: jnp.ndarray, part: jnp.ndarray, k: int
                     ) -> jnp.ndarray:
    """lambda(e) for each edge: number of distinct blocks among the
    (valid) pins.  pins: [M, S] int32, pad = -1.  Returns [M] int32."""
    valid = pins >= 0
    p = part[jnp.clip(pins, 0, part.shape[0] - 1)]          # [M, S]
    onehot = jax.nn.one_hot(p, k, dtype=jnp.int32) * valid[..., None]
    present = (onehot.sum(axis=1) > 0)                       # [M, k]
    return present.sum(axis=-1).astype(jnp.int32)


def cutsize_ref(pins: jnp.ndarray, part: jnp.ndarray,
                edge_weights: jnp.ndarray, k: int) -> jnp.ndarray:
    lam = connectivity_ref(pins, part, k)
    return jnp.where(lam > 1, edge_weights, 0.0).sum()


def gain_gather_ref(incident: jnp.ndarray, becomes_internal: jnp.ndarray,
                    was_internal: jnp.ndarray) -> jnp.ndarray:
    """FM gain assembly: for each vertex, sum the per-edge gain rows of
    its incident edges.

    incident: [N, D] int32 edge ids, pad = -1
    becomes_internal: [M, k] f32 ;  was_internal: [M] f32
    returns gains [N, k] f32  ==  sum_e bi[e] - sum_e wi[e]
    """
    valid = (incident >= 0)[..., None]
    idx = jnp.clip(incident, 0, becomes_internal.shape[0] - 1)
    bi = becomes_internal[idx] * valid                       # [N, D, k]
    wi = was_internal[idx] * valid[..., 0]                   # [N, D]
    return bi.sum(axis=1) - wi.sum(axis=1, keepdims=True)


def gain_stream_ref(incident: jnp.ndarray, becomes_internal: jnp.ndarray,
                    was_internal: jnp.ndarray, block_m: int = 128
                    ) -> jnp.ndarray:
    """Tile-order oracle for the streaming kernel: same result as
    ``gain_gather_ref`` but accumulated edge-tile by edge-tile, pinning
    down the accumulation semantics ``gain_stream_pallas`` must follow
    (each tile contributes its table window times the tile's
    incidence-count matrix)."""
    m, k = becomes_internal.shape
    m_pad = -(-m // block_m) * block_m
    bit = jnp.pad(becomes_internal, ((0, m_pad - m), (0, 0))).T   # [k, M]
    wi = jnp.pad(was_internal, (0, m_pad - m))[None]              # [1, M]
    out = jnp.zeros((k, incident.shape[0]), jnp.float32)
    for lo in range(0, m_pad, block_m):
        rows = jnp.arange(lo, lo + block_m, dtype=jnp.int32)[:, None]
        counts = jnp.zeros((block_m, incident.shape[0]), jnp.float32)
        for d in range(incident.shape[1]):
            counts = counts + (incident[:, d][None, :] == rows)
        w_bi, w_wi = bit[:, lo:lo + block_m], wi[:, lo:lo + block_m]
        hi = jax.lax.Precision.HIGHEST
        out = out + (jnp.dot(w_bi, counts, precision=hi)
                     - jnp.dot(w_wi, counts, precision=hi))
    return out.T


def gain_gather_batch_ref(incident: jnp.ndarray,
                          becomes_internal: jnp.ndarray,
                          was_internal: jnp.ndarray) -> jnp.ndarray:
    """Population-batched gain assembly oracle: incident [N, D] shared,
    bi [alpha, M, k], wi [alpha, M] -> gains [alpha, N, k]."""
    return jax.vmap(lambda bi, wi: gain_gather_ref(incident, bi, wi))(
        becomes_internal, was_internal)


def rating_segment_sum_ref(vals: jnp.ndarray, segs: jnp.ndarray,
                           num_segments: int) -> jnp.ndarray:
    """Ground truth for the pair-rating aggregation: plain segment-sum
    (ids < 0 dropped)."""
    ok = segs >= 0
    return jax.ops.segment_sum(jnp.where(ok, vals, 0.0),
                               jnp.where(ok, segs, num_segments - 1),
                               num_segments=num_segments)


def rating_segment_sum_batch_ref(vals: jnp.ndarray, segs: jnp.ndarray,
                                 num_segments: int) -> jnp.ndarray:
    """Population-batched rating aggregation oracle: vals [alpha, C] per
    member, segs [C] shared -> [alpha, num_segments] (per-row identical
    to ``rating_segment_sum_ref``)."""
    return jax.vmap(lambda v: rating_segment_sum_ref(v, segs,
                                                     num_segments))(vals)


def rating_scatter_ref(vals: jnp.ndarray, segs: jnp.ndarray,
                       num_segments: int, block_c: int = 128) -> jnp.ndarray:
    """Tile-order oracle for ``rating_scatter_pallas``: identical result,
    accumulated candidate-tile by candidate-tile — pins down the
    accumulation semantics the kernel's ``out_ref += partial`` follows."""
    out = jnp.zeros(num_segments, jnp.float32)
    c = vals.shape[0]
    for lo in range(0, c, block_c):
        s = segs[lo:lo + block_c]
        v = vals[lo:lo + block_c]
        ok = (s >= 0) & (s < num_segments)
        out = out + jnp.zeros(num_segments, jnp.float32).at[
            jnp.where(ok, s, 0)].add(jnp.where(ok, v, 0.0))
    return out


def embedding_bag_ref(table: jnp.ndarray, indices: jnp.ndarray,
                      combiner: str = "sum") -> jnp.ndarray:
    """EmbeddingBag: gather + segment-reduce over the bag dimension.

    table: [R, D] ; indices: [B, L] int32, pad = -1 ; returns [B, D].
    """
    valid = (indices >= 0)[..., None]                        # [B, L, 1]
    rows = table[jnp.clip(indices, 0, table.shape[0] - 1)]   # [B, L, D]
    out = (rows * valid).sum(axis=1)
    if combiner == "mean":  # fixed-length-bag mean: pads count (see kernel)
        out = out / indices.shape[1]
    return out
