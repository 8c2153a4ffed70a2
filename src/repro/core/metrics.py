"""Partition quality metrics, all jit-friendly fixed-shape JAX.

Everything is computed from flat pin arrays with segment reductions.
Partition vectors are int32 ``[n_pad]``; the ghost vertex (``n_pad - 1``)
must carry a valid block id (any) and zero weight, so it never affects
weights; ghost pins point at the ghost edge (zero weight), so they never
affect cut terms.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .hypergraph import HypergraphArrays


def member_arrays(hga: HypergraphArrays, ew_row: jnp.ndarray
                  ) -> HypergraphArrays:
    """One mutation-cohort member's view of a shared-structure hypergraph
    (DESIGN.md §10): every structural leaf broadcast, only the
    edge-weight leaf swapped for the member's row."""
    return dataclasses.replace(hga, edge_weights=ew_row)


def block_weights(hga: HypergraphArrays, part: jnp.ndarray, k: int) -> jnp.ndarray:
    """[k] total vertex weight per block."""
    return jax.ops.segment_sum(hga.vertex_weights, part, num_segments=k)


def pins_in_block(hga: HypergraphArrays, part: jnp.ndarray, k: int,
                  pin_axis: str | None = None) -> jnp.ndarray:
    """Phi [m_pad, k]: for each edge, how many of its pins are in block j.

    ``pin_axis``: when the pin tables are row-sharded over a mesh axis
    (DESIGN.md §15) this runs on the local rows and psums the int32
    partial counts — integer addition commutes exactly, so the summed
    Phi is bit-equal to the replicated computation (the
    ``population._phi`` template)."""
    pin_parts = part[hga.pin_vertex]                      # [P]
    flat = hga.pin_edge.astype(jnp.int32) * k + pin_parts
    counts = jax.ops.segment_sum(
        jnp.ones_like(flat, jnp.int32), flat, num_segments=hga.m_pad * k
    )
    counts = counts.reshape(hga.m_pad, k)
    if pin_axis is not None:
        counts = jax.lax.psum(counts, pin_axis)
    return counts


def connectivity(hga: HypergraphArrays, part: jnp.ndarray, k: int,
                 pin_axis: str | None = None) -> jnp.ndarray:
    """lambda(e) [m_pad]: number of distinct blocks spanned by each edge."""
    phi = pins_in_block(hga, part, k, pin_axis=pin_axis)
    return (phi > 0).sum(axis=-1).astype(jnp.int32)


def cutsize(hga: HypergraphArrays, part: jnp.ndarray, k: int,
            pin_axis: str | None = None) -> jnp.ndarray:
    """Sum of weights of edges spanning >= 2 blocks (the paper's objective)."""
    lam = connectivity(hga, part, k, pin_axis=pin_axis)
    return jnp.where(lam > 1, hga.edge_weights, 0.0).sum()


def km1(hga: HypergraphArrays, part: jnp.ndarray, k: int) -> jnp.ndarray:
    """(lambda - 1) connectivity objective (KaHyPar's other metric)."""
    lam = connectivity(hga, part, k)
    return (jnp.maximum(lam - 1, 0).astype(jnp.float32) * hga.edge_weights).sum()


def balance_cap(total_weight, k: int, eps: float) -> jnp.ndarray:
    """The paper's constraint: W_i <= (1+eps) * ceil(W/k)."""
    return (1.0 + eps) * jnp.ceil(total_weight / k)


def is_balanced(hga: HypergraphArrays, part: jnp.ndarray, k: int, eps: float):
    bw = block_weights(hga, part, k)
    return (bw <= balance_cap(hga.total_weight, k, eps) + 1e-4).all()


def imbalance(hga: HypergraphArrays, part: jnp.ndarray, k: int) -> jnp.ndarray:
    bw = block_weights(hga, part, k)
    avg = hga.total_weight / k
    return bw.max() / jnp.maximum(avg, 1e-9) - 1.0


# --------------------------------------------------------------------------
# FM move gains
# --------------------------------------------------------------------------
def _edge_gain_terms(hga: HypergraphArrays, phi: jnp.ndarray):
    """Per-edge FM terms (stage 1 of the gain pipeline):
    becomes_internal [m_pad, k] and was_internal [m_pad]."""
    sizes = hga.edge_sizes[:, None]
    w = hga.edge_weights[:, None]
    becomes_internal = jnp.where(phi == sizes - 1, w, 0.0)
    was_internal = jnp.where((phi == sizes) & (sizes > 0), w, 0.0).sum(-1)
    return becomes_internal, was_internal


def _gain_segsum(hga: HypergraphArrays, phi: jnp.ndarray,
                 pin_axis: str | None = None) -> jnp.ndarray:
    """XLA reference assembly: per-pin gather + segment-sum.  Materialises
    a [P, k] intermediate — fine for small k, the fallback everywhere.

    With ``pin_axis`` the gathers run over the local pin rows and the two
    segment-sums become psum'd partials (the ``population._gains``
    template — g and l are psum'd separately).  Edge weights are
    integer-valued f32 on every instance the engines ingest, so the
    partial sums are exact and the summed gains bit-equal the replicated
    assembly (DESIGN.md §15)."""
    becomes_internal, was_internal = _edge_gain_terms(hga, phi)
    per_pin_gain = becomes_internal[hga.pin_edge]          # [P, k]
    per_pin_loss = was_internal[hga.pin_edge]              # [P]
    g = jax.ops.segment_sum(per_pin_gain, hga.pin_vertex,
                            num_segments=hga.n_pad)        # [n_pad, k]
    l = jax.ops.segment_sum(per_pin_loss, hga.pin_vertex,
                            num_segments=hga.n_pad)        # [n_pad]
    if pin_axis is not None:
        g = jax.lax.psum(g, pin_axis)
        l = jax.lax.psum(l, pin_axis)
    return g - l[:, None]


def _gain_compact(hga: HypergraphArrays, phi: jnp.ndarray, k: int,
                  pin_axis: str | None = None) -> jnp.ndarray:
    """Sparse XLA assembly for large k, O(P) instead of O(P * k).

    ``becomes_internal`` has at most TWO nonzero columns per edge: an
    edge of size s >= 3 can have Phi = s-1 in at most one block (the
    counts sum to s), a size-2 edge in at most two, and size <= 1 edges
    contribute exactly zero net gain off the diagonal (becoming internal
    at j is paid back by leaving the block where they were internal), so
    they are dropped entirely.  The two (column, weight) pairs per edge
    scatter through the pins straight into the [n_pad, k] gain table —
    no [P, k] or [m_pad, k]-gather intermediate.  The scatter indices
    stay 2-D (vertex row, block column): a flattened ``v * k + j`` index
    would overflow int32 exactly in the n_pad * k > 2**31 fine-level
    large-k regime this path exists for.
    """
    w = hga.edge_weights
    s = hga.edge_sizes[:, None]
    multi = hga.edge_sizes >= 2
    mask = (phi == s - 1) & multi[:, None]                 # <=2 true per row
    cols = jnp.arange(k, dtype=jnp.int32)[None, :]
    c1 = jnp.min(jnp.where(mask, cols, k), axis=1)         # k = "none"
    c2 = jnp.min(jnp.where(mask & (cols != c1[:, None]), cols, k), axis=1)
    was_internal = jnp.where((phi == s) & multi[:, None], w[:, None],
                             0.0).sum(-1)

    pe, pv = hga.pin_edge, hga.pin_vertex
    # "none" columns land at j == k, out of bounds -> dropped by the mode
    g = (jnp.zeros((hga.n_pad, k), jnp.float32)
         .at[pv, c1[pe]].add(w[pe], mode="drop")
         .at[pv, c2[pe]].add(w[pe], mode="drop"))
    l = jax.ops.segment_sum(was_internal[pe], pv, num_segments=hga.n_pad)
    if pin_axis is not None:
        # sharded pin rows: g and l are per-shard partials (psum'd
        # separately, like _gain_segsum / population._gains)
        g = jax.lax.psum(g, pin_axis)
        l = jax.lax.psum(l, pin_axis)
    return g - l[:, None]


def pin_multiplicity(hga: HypergraphArrays) -> jnp.ndarray:
    """H [n_pad, m_pad] bf16, the level's dense incidence: H[v, e] is the
    number of pins of v in e, built with one scatter of the pins.  Exact
    while a vertex holds at most 256 pins of one net (bf16 holds every
    integer up to 256).  Ghost pins pile onto (ghost vertex, ghost edge),
    where the count may round: that edge's weight and size are 0, so
    every gain term it meets is 0."""
    return jnp.zeros((hga.n_pad, hga.m_pad), jnp.bfloat16).at[
        hga.pin_vertex, hga.pin_edge].add(jnp.bfloat16(1))


def _gain_dense(hga: HypergraphArrays, mult: jnp.ndarray, phi: jnp.ndarray,
                k: int) -> jnp.ndarray:
    """[n_pad, k] gains as one contraction of the dense incidence ``mult``
    (``pin_multiplicity``) with the per-edge terms: no scatter, and under
    a vmap over members with ``mult`` unbatched, one matmul that reads
    ``mult`` once for all of them.  The own-block entries are left as
    they come (callers mask them).

    Exactness: the f32 terms are split into three bf16 pieces that sum
    back to them exactly, and each bf16 x bf16 product is exact in the
    f32 accumulator.  With integer edge weights (every instance the
    engines take, see ``_gain_segsum``) and per-vertex sums below 2**24,
    every partial sum is an integer held exactly, so the gains are
    bit-equal to ``_gain_segsum``."""
    becomes_internal, was_internal = _edge_gain_terms(hga, phi)
    x = jnp.concatenate([becomes_internal.T, was_internal[None, :]])
    hi = x.astype(jnp.bfloat16)                         # [k+1, m_pad]
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    # edges minor on both sides: under the members' vmap the batched side
    # folds into the rows of one [members * 3(k+1), m_pad] x [m_pad, n_pad]
    # matmul
    g = jax.lax.dot_general(jnp.concatenate([hi, mid, lo]), mult,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    g = g[:k + 1] + g[k + 1:2 * (k + 1)] + g[2 * (k + 1):]  # [k+1, n_pad]
    return (g[:k] - g[k:]).T


def _resolve_gain_path(hga: HypergraphArrays, k: int, assemble: str) -> str:
    """Static (trace-time) path choice: "auto" consults the ops
    dispatcher by (k, backend); a concrete path name forces it.  The FM
    move loop assembles its own gains (``refine._fm_pass_impl``)."""
    from repro.kernels import ops
    if assemble == "auto":
        return ops.gain_path(k, incidence=hga.incident is not None)
    return assemble


def gain_matrix(hga: HypergraphArrays, part: jnp.ndarray, k: int,
                phi: jnp.ndarray | None = None,
                assemble: str = "auto",
                pin_axis: str | None = None) -> jnp.ndarray:
    """Full [n_pad, k] cut-size gain matrix.

    gain[v, j] = reduction in cut if v moves from part[v] to j
               = sum_{e in I(v)} w_e * ( [Phi(e,j) == |e|-1]  (becomes internal)
                                        - [Phi(e,part[v]) == |e|] (was internal) )
    gain[v, part[v]] == 0 by construction.

    Assembly is routed through the ``kernels.ops`` gain dispatcher (see
    its docstring for the decision table): the streaming Pallas kernel
    on compiled backends, segment-sum or the compact sparse path on
    CPU.  All paths agree to float tolerance; within one path the
    scalar and vmapped population entry points agree bit-for-bit.
    """
    if phi is None:
        phi = pins_in_block(hga, part, k, pin_axis=pin_axis)  # [m_pad, k]
    path = _resolve_gain_path(hga, k, assemble)
    if pin_axis is not None and path not in ("segsum", "compact"):
        # kernel assembly indexes the dense incidence layout by GLOBAL
        # pin position; on row-sharded pins only the XLA partial paths
        # exist (model-shard placement drops the layout anyway)
        path = "segsum"
    if path == "compact":
        g = _gain_compact(hga, phi, k, pin_axis=pin_axis)
    elif path == "segsum" or hga.incident is None:
        g = _gain_segsum(hga, phi, pin_axis=pin_axis)
    else:
        from repro.kernels import ops
        bi, wi = _edge_gain_terms(hga, phi)
        g = ops.gain_assemble(hga.incident, bi, wi, path)  # [n_pad, k]
    # moving to your own block is never a move
    g = g.at[jnp.arange(hga.n_pad), part].set(0.0)
    return g


# --------------------------------------------------------------------------
# Similarity metrics between partitions (paper Sec. 3.2)
# --------------------------------------------------------------------------
def node_distance(part_a: jnp.ndarray, part_b: jnp.ndarray,
                  valid_n: int | None = None) -> jnp.ndarray:
    """Hamming distance d_v — susceptible to partition isomorphism."""
    neq = (part_a != part_b).astype(jnp.int32)
    if valid_n is not None:
        neq = neq * (jnp.arange(part_a.shape[0]) < valid_n)
    return neq.sum()


def edge_distance(hga: HypergraphArrays, part_a: jnp.ndarray,
                  part_b: jnp.ndarray, k: int) -> jnp.ndarray:
    """Label-invariant d_e: L1 distance between connectivity vectors."""
    la = connectivity(hga, part_a, k)
    lb = connectivity(hga, part_b, k)
    valid = jnp.arange(hga.m_pad) < hga.m
    return jnp.where(valid, jnp.abs(la - lb), 0).sum()


def cut_edge_indicator(hga: HypergraphArrays, part: jnp.ndarray, k: int):
    """[m_pad] 1.0 where the edge is cut (used by mutation reweighting)."""
    lam = connectivity(hga, part, k)
    return (lam > 1).astype(jnp.float32)


# --------------------------------------------------------------------------
# Population-batched variants: parts is [alpha, n_pad], one hypergraph
# shared by all members.  These are the building blocks of the batched
# refinement engine (refine.lp_refine_population et al.) — one XLA
# dispatch covers the whole population.
# --------------------------------------------------------------------------
def _over_parts(fn):
    """vmap a (hga, part, k) metric over a leading population axis."""
    return jax.vmap(fn, in_axes=(None, 0, None))


block_weights_population = jax.jit(
    _over_parts(block_weights), static_argnums=2)       # [alpha, k]
pins_in_block_population = jax.jit(
    _over_parts(pins_in_block), static_argnums=2)       # [alpha, m_pad, k]
connectivity_population = jax.jit(
    _over_parts(connectivity), static_argnums=2)        # [alpha, m_pad]
cutsize_population = jax.jit(
    _over_parts(cutsize), static_argnums=2)             # [alpha]


def _cutsize_population_weighted_impl(hga: HypergraphArrays,
                                      parts: jnp.ndarray,
                                      ew_pop: jnp.ndarray, k: int,
                                      pin_axis: str | None = None
                                      ) -> jnp.ndarray:
    return jax.vmap(
        lambda p, ew: cutsize(member_arrays(hga, ew), p, k,
                              pin_axis=pin_axis))(parts, ew_pop)


#: [alpha] cuts where each member is measured with ITS OWN edge-weight
#: row ``ew_pop[alpha, m_pad]`` over the shared structure — the mutation
#: cohort's objective (each flagged member optimises its own reweight).
cutsize_population_weighted = jax.jit(
    _cutsize_population_weighted_impl, static_argnums=3)


def _gain_matrix_population_impl(hga: HypergraphArrays, parts: jnp.ndarray,
                                 k: int, assemble: str = "auto",
                                 ew_pop: jnp.ndarray | None = None,
                                 pin_axis: str | None = None
                                 ) -> jnp.ndarray:
    """Population gain matrices [alpha, n_pad, k] in one dispatch.

    XLA paths vmap the scalar ``gain_matrix`` (bit-identical per lane);
    kernel paths call the explicitly alpha-gridded batch kernels instead
    of vmapping a ``pallas_call`` (same tile program per member, so each
    member still matches its single-member launch bit-for-bit).

    ``ew_pop`` [alpha, m_pad] (optional) gives every member its own
    edge-weight row over the shared structure (mutation cohort): weights
    only enter through the per-edge gain terms, so the kernel paths keep
    the one shared incidence layout and simply stream per-member tables.
    """
    path = _resolve_gain_path(hga, k, assemble)
    if path in ("segsum", "compact") or hga.incident is None \
            or pin_axis is not None:
        if ew_pop is None:
            return _over_parts(
                lambda h, p, kk: gain_matrix(h, p, kk, assemble=path,
                                             pin_axis=pin_axis))(
                    hga, parts, k)
        return jax.vmap(
            lambda p, ew: gain_matrix(member_arrays(hga, ew), p, k,
                                      assemble=path, pin_axis=pin_axis))(
                parts, ew_pop)
    from repro.kernels import ops
    phi = _over_parts(pins_in_block)(hga, parts, k)     # [alpha, m_pad, k]
    if ew_pop is None:
        bi, wi = jax.vmap(_edge_gain_terms, in_axes=(None, 0))(hga, phi)
    else:
        bi, wi = jax.vmap(
            lambda ew, ph: _edge_gain_terms(member_arrays(hga, ew), ph))(
                ew_pop, phi)
    g = ops.gain_assemble_batch(hga.incident, bi, wi, path)
    return jax.vmap(
        lambda gg, p: gg.at[jnp.arange(hga.n_pad), p].set(0.0))(g, parts)


gain_matrix_population = jax.jit(
    _gain_matrix_population_impl,
    static_argnames=("k", "assemble"))                  # [alpha, n_pad, k]


@partial(jax.jit, static_argnames=("k",))
def edge_distance_matrix(hga: HypergraphArrays, parts: jnp.ndarray, k: int
                         ) -> jnp.ndarray:
    """All-pairs label-invariant d_e between population members:
    one batched connectivity dispatch instead of alpha^2 pairwise calls.
    Returns [alpha, alpha] int32."""
    lam = _over_parts(connectivity)(hga, parts, k)       # [alpha, m_pad]
    valid = (jnp.arange(hga.m_pad) < hga.m)[None, None, :]
    diff = jnp.abs(lam[:, None, :] - lam[None, :, :])
    return jnp.where(valid, diff, 0).sum(-1).astype(jnp.int32)


# Convenient jitted entry points (k is static)
cutsize_jit = jax.jit(cutsize, static_argnums=2)
km1_jit = jax.jit(km1, static_argnums=2)
connectivity_jit = jax.jit(connectivity, static_argnums=2)
gain_matrix_jit = jax.jit(gain_matrix, static_argnames=("k", "assemble"))
edge_distance_jit = jax.jit(edge_distance, static_argnums=3)
block_weights_jit = jax.jit(block_weights, static_argnums=2)
