"""Device-resident coarsening engine (DESIGN.md §3).

The host coarsener (``core/coarsen``) rates, matches and contracts in
numpy and then re-ships every level to the device for refinement.  This
module runs the identical per-round pipeline as jitted JAX ops on fixed
padded shapes, so every level's ``HypergraphArrays`` is *born on
device* and uncoarsening never pays a host->device transfer:

1. **pair rating** — heavy-edge candidates from stride-shifted views of
   the edge-contiguous pin array (full coverage for small edges, a
   structured sample for large ones, exactly like the host
   ``_candidate_pairs``); duplicate pairs are made adjacent with two
   stable argsorts and their ratings ``r(u, v) = sum_e w_e / (|e| - 1)``
   aggregated through the ``kernels.ops.rating_segment_sum`` dispatcher
   (Pallas MXU scatter kernel on compiled backends for coarse/mid
   rounds, XLA segment-sum otherwise), then normalised by
   ``c(u) * c(v)``;
2. **best-partner mutual matching** — argmax by scatter-max with
   reproducible tie-jitter from a threaded PRNG key, weight-cap
   filtering, mutual-pair extraction and the same single-vertex second
   chance the host matcher gives, then dense renumbering by cumsum;
3. **contraction** — ``hypergraph.contract_arrays`` (within-edge pin
   dedup, single-pin drop, identical-edge merge, dense edge renumber).

Both engines derive their control flow from one ``coarsen.round_schedule``
— same contraction target, same cluster-weight cap, same stall rule — so
the parity harness (``tests/test_dcoarsen.py``) checks cut parity of the
resulting hierarchies knowing only tie-breaking differs.

``REPRO_COARSEN_PATH=device|host`` forces an engine; ``auto`` (unset)
picks the device engine on compiled backends and keeps the numpy
reference path on CPU.  ``build_hierarchy`` is the single entry point —
``impart_partition``, ``vcycle`` (and through it recombination) route
through it and consume either hierarchy via the shared protocol.

The mutation cohort takes a third road (DESIGN.md §10):
``population_coarsen`` builds ONE shared-structure hierarchy for all
flagged members at once — candidate pairs restricted to vertices that
are same-block in EVERY member (so every member's partition projects
cut-exactly through every level), per-member heavy-edge ratings
aggregated in one batched dispatch (``ops.rating_segment_sum_batch``),
one consensus matching from the summed member ratings, one contraction
that pushes every member's edge-weight row through the same edge map.
Structure leaves are broadcast; only edge weights and partitions carry
the alpha axis.  The round schedule is the same ``coarsen.round_schedule``
— it depends only on vertex weights and structure, which the cohort
shares by construction — so one jitted round serves all members.
"""
from __future__ import annotations

import dataclasses
import os
from functools import lru_cache, partial
from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import obs
from ..jaxcompat import shard_map
from .hypergraph import (Hypergraph, HypergraphArrays, HierarchyArrays,
                         DeviceLevel, contract_arrays, _round_pow2,
                         _INCIDENCE_LANE_PAD, _INCIDENCE_MAX_EXPANSION)
from .coarsen import Hierarchy, coarsen, round_schedule
from . import popshard

#: Pair-candidate sampling, mirroring the host ``_candidate_pairs``
#: defaults: strides 1..MAX_STRIDE within each edge; edges larger than
#: MAX_EDGE_SIZE carry almost no locality signal and are skipped.
MAX_STRIDE = 4
MAX_EDGE_SIZE = 512

COARSEN_PATHS = ("device", "host")


def coarsen_path() -> str:
    """Engine selection: ``REPRO_COARSEN_PATH=device|host`` forces one;
    auto keeps the numpy reference on CPU and goes device-resident on
    compiled backends."""
    env = os.environ.get("REPRO_COARSEN_PATH", "auto").strip().lower()
    if env in COARSEN_PATHS:
        return env
    if env not in ("", "auto"):
        from repro.env import warn_env_once
        warn_env_once("REPRO_COARSEN_PATH", env, "auto routing")
    from repro.kernels import ops
    return "host" if ops.interpret_mode() else "device"


def build_hierarchy(hg: Hypergraph, k: int, *, seed: int = 0,
                    restrict_part=None, contraction_limit_factor: int = 64,
                    max_rounds: int = 64, min_shrink: float = 0.02,
                    max_cluster_frac: float = 1.0,
                    path: Optional[str] = None,
                    model_shard: Optional[str] = None
                    ) -> Union[Hierarchy, HierarchyArrays]:
    """Build the multilevel hierarchy with the engine picked by
    ``coarsen_path()`` (or forced via ``path``).  Both return types
    implement the hierarchy protocol the drivers consume."""
    path = path or coarsen_path()
    if path == "host":
        return coarsen(hg, k, contraction_limit_factor=contraction_limit_factor,
                       max_rounds=max_rounds, min_shrink=min_shrink,
                       seed=seed, restrict_part=restrict_part,
                       max_cluster_frac=max_cluster_frac)
    return device_coarsen(hg, k,
                          contraction_limit_factor=contraction_limit_factor,
                          max_rounds=max_rounds, min_shrink=min_shrink,
                          seed=seed, restrict_part=restrict_part,
                          max_cluster_frac=max_cluster_frac,
                          model_shard=model_shard)


# --------------------------------------------------------------------------
# the jitted round: rate -> match -> contract
# --------------------------------------------------------------------------
def _stride_candidates(hga: HypergraphArrays, *, max_stride: int,
                       max_edge_size: int):
    """Stride-shifted candidate pairs over the edge-contiguous pin array,
    shared by the scalar and population rating paths (one source for the
    coverage/sampling policy, so the engines cannot desynchronise).

    Returns ``(u, v, valid, pe_cat)``, each [C = max_stride * p_pad]:
    the raw endpoints, the STRUCTURE-only validity mask (same edge,
    rateable edge size, distinct endpoints — callers AND in their
    partition restriction), and the edge id of every candidate slot.
    """
    m_pad = hga.m_pad
    ghost_v = jnp.int32(hga.n_pad - 1)
    pv, pe = hga.pin_vertex, hga.pin_edge
    sizes = hga.edge_sizes
    ok_edge = (sizes > 1) & (sizes <= max_edge_size)
    us, vs, valids = [], [], []
    for d in range(1, max_stride + 1):
        u = pv
        v = jnp.concatenate([pv[d:], jnp.full(d, ghost_v, jnp.int32)])
        e2 = jnp.concatenate([pe[d:],
                              jnp.full(d, m_pad - 1, jnp.int32)])
        us.append(u)
        vs.append(v)
        valids.append((pe == e2) & ok_edge[pe] & (u != v))
    return (jnp.concatenate(us), jnp.concatenate(vs),
            jnp.concatenate(valids), jnp.tile(pe, max_stride))


def _pair_ratings(hga: HypergraphArrays, part, *, max_stride: int,
                  max_edge_size: int):
    """Aggregated, weight-normalised heavy-edge pair ratings.

    Returns ``(lo, hi, rating)``, each [C = max_stride * p_pad]: one
    slot per *distinct* candidate pair (at its first sorted position),
    ghost slots carrying ``lo == hi == n_pad - 1`` and rating 0.
    ``part`` (optional) restricts candidates to same-block pairs
    (partition-aware / V-cycle coarsening).
    """
    from repro.kernels import ops
    n_pad = hga.n_pad
    ghost_v = jnp.int32(n_pad - 1)
    sizes = hga.edge_sizes
    unit = jnp.where(sizes > 1,
                     hga.edge_weights / jnp.maximum(sizes - 1, 1), 0.0)
    u, v, valid, pe_cat = _stride_candidates(
        hga, max_stride=max_stride, max_edge_size=max_edge_size)
    if part is not None:
        valid = valid & (part[u] == part[v])
    lo = jnp.where(valid, jnp.minimum(u, v), ghost_v)
    hi = jnp.where(valid, jnp.maximum(u, v), ghost_v)
    r = jnp.where(valid, unit[pe_cat], 0.0)

    # make duplicate pairs adjacent (ghosts sort last: lo == hi == ghost);
    # one variadic sort carrying the ratings — aggregation is
    # order-insensitive, so no stability is needed
    lo, hi, r = jax.lax.sort((lo, hi, r), num_keys=2, is_stable=False)
    c = lo.shape[0]
    newg = jnp.ones(c, bool).at[1:].set(
        (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1]))
    seg = (jnp.cumsum(newg.astype(jnp.int32)) - 1).astype(jnp.int32)
    agg = ops.rating_segment_sum(r, seg, c)

    # representative (lo, hi) per segment + weight normalisation
    lo_g = jnp.full(c, ghost_v, jnp.int32).at[seg].min(lo)
    hi_g = jnp.full(c, ghost_v, jnp.int32).at[seg].min(hi)
    cw = hga.vertex_weights
    agg = agg / jnp.maximum(cw[lo_g] * cw[hi_g], 1e-12)
    return lo_g, hi_g, agg


def _mutual_match_dev(hga: HypergraphArrays, lo: jnp.ndarray,
                      hi: jnp.ndarray, rating: jnp.ndarray,
                      key: jnp.ndarray, c_max: jnp.ndarray):
    """Best-partner mutual matching on device.

    Same structure as the host ``_mutual_match`` — both directions,
    reproducible rating tie-jitter, weight cap, mutual pairs, second
    chance for singles whose best partner stayed single — with scatter
    argmax/argmin replacing the lexsorts (tie-break order may differ
    from the host; cut parity is the contract, not bit-equal matchings).
    Returns ``(cid, n_new)``: dense cluster ids [n_pad] (ghost/pad slots
    -> ``n_pad - 1``).
    """
    n_pad = hga.n_pad
    arange = jnp.arange(n_pad, dtype=jnp.int32)
    cw = hga.vertex_weights

    uu = jnp.concatenate([lo, hi])
    vv = jnp.concatenate([hi, lo])
    # tie-jitter must be visible at f32 resolution (the host jitters
    # 1e-9 in float64; here 1 + 1e-9 would round to exactly 1.0 and the
    # key would have no effect) — 1e-6 relative stays far below any real
    # rating difference while making ties key-dependent
    jit_r = 1.0 + 1e-6 * jax.random.uniform(key, uu.shape)
    rr = jnp.concatenate([rating, rating]) * jit_r
    ok = (jnp.concatenate([lo, lo]) != jnp.concatenate([hi, hi])) \
        & (cw[uu] + cw[vv] <= c_max) & (rr > 0)

    score = jnp.where(ok, rr, -1.0)
    best = jnp.full(n_pad, -1.0).at[uu].max(score)
    hit = ok & (score == best[uu])
    partner = jnp.full(n_pad, n_pad, jnp.int32).at[uu].min(
        jnp.where(hit, vv, n_pad))
    has = partner < n_pad
    p_of = jnp.where(has, partner, 0)
    mutual = has & (partner[p_of] == arange) & (partner != arange)
    cluster = jnp.where(mutual & (arange > partner), p_of, arange)

    # second chance: unmatched vertex whose best partner stayed single
    single = (cluster == arange) & ~mutual
    cand = single & has
    tgt = jnp.where(cand, p_of, n_pad - 1)
    tgt_ok = single[tgt] & (cw[arange] + cw[tgt] <= c_max) & (tgt != arange)
    want = cand & tgt_ok
    winner = jnp.full(n_pad, n_pad, jnp.int32).at[tgt].min(
        jnp.where(want, arange, n_pad))
    win = want & (winner[tgt] == arange)
    # a chosen target must not itself be a source
    sel = win & ~win[tgt]
    cluster = jnp.where(sel, tgt, cluster)

    # dense renumbering (roots keep ascending order, like np.unique)
    is_root = (cluster == arange) & (arange < hga.n)
    new_id = (jnp.cumsum(is_root.astype(jnp.int32)) - 1).astype(jnp.int32)
    n_new = is_root.sum()
    cid = jnp.where(arange < hga.n, new_id[cluster], jnp.int32(n_pad - 1))
    return cid, n_new


def _coarsen_round_impl(hga: HypergraphArrays, part, key, c_max,
                        max_stride: int, max_edge_size: int):
    lo, hi, rating = _pair_ratings(hga, part, max_stride=max_stride,
                                   max_edge_size=max_edge_size)
    cid, n_new = _mutual_match_dev(hga, lo, hi, rating, key, c_max)
    coarse, p_new = contract_arrays(hga, cid, n_new)
    new_part = None
    if part is not None:
        # block of each cluster = block of any member (same by constr.)
        new_part = jnp.zeros(hga.n_pad, jnp.int32).at[cid].max(part)
    return coarse, cid, new_part, p_new


_coarsen_round = jax.jit(_coarsen_round_impl,
                         static_argnames=("max_stride", "max_edge_size"))


# --------------------------------------------------------------------------
# model-axis sharded contraction (DESIGN.md §15): shard-local contraction
# over row-sharded pin tables with a lax.ppermute halo for cut edges
# --------------------------------------------------------------------------
def _hga_model_pspecs() -> HypergraphArrays:
    """PartitionSpec pytree for a model-sharded structure: pin tables
    row-sharded over "model", every [n_pad]/[m_pad] leaf replicated."""
    return HypergraphArrays(
        pin_vertex=P("model"), pin_edge=P("model"),
        vertex_weights=P(), edge_weights=P(), edge_sizes=P(),
        n=P(), m=P(), incident=None)


def _contract_sharded_body(hga: HypergraphArrays, cid, n_new, ew_pop,
                           S: int):
    """Shard-local ``contract_arrays`` over [p_pad / S] pin rows.

    Runs inside ``shard_map`` over the mesh "model" axis.  The input pin
    arrays are edge-contiguous (the level invariant every producer
    maintains), so an edge's pins occupy one contiguous global run; the
    edge is OWNED by the shard holding its first pin, and — guarded by
    the caller's ``max edge size <= p_loc`` check — the owner's local
    rows plus ONE ``lax.ppermute`` halo (the right neighbour's full
    window, mirroring the pop-axis ring of ``popshard.ring_partners``)
    always contain the whole edge.  Pins of non-owned edges in the
    window are masked to ghosts, so dedup / sizing / position ranks and
    the parallel-edge hashes are computed on complete edges shard-
    locally; the int32/uint32 per-edge partials then ``psum`` exactly
    (integer adds are associative), after which every [m_pad] decision
    (merge groups, survivors, dense renumber) is replicated-identical —
    the same partial-sum pattern as ``population._phi``/``_gains``.
    Ownership is monotone in edge id (first-pin position is), so
    scattering each shard's kept pins at its psum'd global offset
    reassembles the exact (edge, vertex)-sorted pin order the unsharded
    ``contract_arrays`` emits: the result is bit-equal, ghosts and all.
    """
    n_pad, m_pad = hga.n_pad, hga.m_pad
    p_loc = hga.pin_vertex.shape[0]
    p_pad = p_loc * S
    ghost_v = jnp.int32(n_pad - 1)
    ghost_e = jnp.int32(m_pad - 1)
    arange_m = jnp.arange(m_pad, dtype=jnp.int32)
    idx = jax.lax.axis_index("model")

    new_vw = jnp.zeros(n_pad, jnp.float32).at[cid].add(hga.vertex_weights)

    # edge ownership = shard of the edge's first global pin
    pvc = cid[hga.pin_vertex]
    pe_l = hga.pin_edge
    live_l = pe_l != ghost_e
    gpos = idx * p_loc + jnp.arange(p_loc, dtype=jnp.int32)
    first_partial = jnp.full(m_pad, p_pad, jnp.int32).at[pe_l].min(
        jnp.where(live_l, gpos, p_pad))
    owner = jax.lax.pmin(first_partial, "model") // p_loc

    # halo: the right neighbour's whole window (full ring, no zero-fill;
    # the wraparound halo shard S-1 receives holds only shard-0-owned
    # edges, which the ownership mask drops)
    perm = [(j, (j - 1) % S) for j in range(S)]
    pv_e = jnp.concatenate([pvc, jax.lax.ppermute(pvc, "model", perm)])
    pe_e = jnp.concatenate([pe_l, jax.lax.ppermute(pe_l, "model", perm)])
    mine = (pe_e != ghost_e) & (owner[pe_e] == idx)
    pv_e = jnp.where(mine, pv_e, ghost_v)
    pe_e = jnp.where(mine, pe_e, ghost_e)

    # local (edge, vertex) sort + within-edge dedup — every owned edge is
    # complete in the window, so this is the global dedup restricted to
    # the shard's own edges
    pe_s, pv_s = jax.lax.sort((pe_e, pv_e), num_keys=2, is_stable=False)
    two_p = 2 * p_loc
    dup = jnp.zeros(two_p, bool).at[1:].set(
        (pe_s[1:] == pe_s[:-1]) & (pv_s[1:] == pv_s[:-1])
        & (pe_s[1:] != ghost_e))
    pv_s = jnp.where(dup, ghost_v, pv_s)
    pe_s = jnp.where(dup, ghost_e, pe_s)

    # post-dedup sizes: owner-only int32 partials, psum'd exact
    live_pin = pe_s != ghost_e
    sizes = jnp.zeros(m_pad, jnp.int32).at[pe_s].add(
        live_pin.astype(jnp.int32))
    sizes = jax.lax.psum(sizes, "model")
    edge_alive = (arange_m < hga.m) & (sizes >= 2)
    keep_pin = live_pin & edge_alive[pe_s]
    pv_s = jnp.where(keep_pin, pv_s, ghost_v)
    pe_s = jnp.where(keep_pin, pe_s, ghost_e)

    # parallel-edge hashes: positions are within-edge kept ranks, which
    # are local differences (edge complete in window), and the uint32
    # per-pin terms psum exactly — bit-equal to the global hash
    local_rank = jnp.cumsum(keep_pin.astype(jnp.int32)) - 1
    first_rank = jnp.full(m_pad, two_p, jnp.int32).at[pe_s].min(
        jnp.where(keep_pin, local_rank, two_p))
    pos = (local_rank - first_rank[pe_s]).astype(jnp.uint32)
    pu = pv_s.astype(jnp.uint32)
    a1 = (pu + jnp.uint32(0x9E3779B9)) * (pos * jnp.uint32(2)
                                          + jnp.uint32(1))
    a2 = (pu ^ jnp.uint32(0x85EBCA6B)) * (pos + jnp.uint32(0xC2B2AE35))
    m1 = a1 * (a1 >> jnp.uint32(15))
    m2 = a2 ^ (a2 << jnp.uint32(7))
    live_u = keep_pin.astype(jnp.uint32)
    h1 = jax.lax.psum(
        jnp.zeros(m_pad, jnp.uint32).at[pe_s].add(m1 * live_u), "model")
    h2 = jax.lax.psum(
        jnp.zeros(m_pad, jnp.uint32).at[pe_s].add(m2 * live_u), "model")
    su = sizes.astype(jnp.uint32)
    h1 = h1 ^ (su * jnp.uint32(0x27D4EB2F))
    h2 = h2 ^ su
    h1 = jnp.where(edge_alive, h1, jnp.uint32(0xFFFFFFFF))
    h2 = jnp.where(edge_alive, h2, arange_m.astype(jnp.uint32))

    # [m_pad] merge/renumber: replicated-identical on every shard (the
    # f32 weight merge runs on replicated inputs in replicated order —
    # no psum touches it, so no float-summation-order hazard)
    h1s, h2s, eo = jax.lax.sort((h1, h2, arange_m), num_keys=2,
                                is_stable=False)
    newg = jnp.ones(m_pad, bool).at[1:].set(
        (h1s[1:] != h1s[:-1]) | (h2s[1:] != h2s[:-1]))
    grp = jnp.cumsum(newg.astype(jnp.int32)) - 1
    alive_s = edge_alive[eo]
    gw = jnp.zeros(m_pad, jnp.float32).at[grp].add(
        jnp.where(alive_s, hga.edge_weights[eo], 0.0))
    rep = jnp.full(m_pad, m_pad, jnp.int32).at[grp].min(
        jnp.where(alive_s, eo, m_pad))
    grp_of = jnp.zeros(m_pad, jnp.int32).at[eo].set(grp)
    keep_edge = edge_alive & (arange_m == rep[grp_of])
    merged_w = jnp.where(keep_edge, gw[grp_of], 0.0)

    pin_ok = keep_edge[pe_s] & (pe_s != ghost_e)
    pv_s = jnp.where(pin_ok, pv_s, ghost_v)
    pe_s = jnp.where(pin_ok, pe_s, ghost_e)
    new_eid = (jnp.cumsum(keep_edge.astype(jnp.int32)) - 1).astype(
        jnp.int32)
    m_new = keep_edge.sum()
    pe_s = jnp.where(pe_s != ghost_e, new_eid[pe_s], ghost_e)
    tgt = jnp.where(keep_edge, new_eid, ghost_e)
    new_ew = jnp.zeros(m_pad, jnp.float32).at[tgt].add(
        jnp.where(keep_edge, merged_w, 0.0))
    new_es = jnp.zeros(m_pad, jnp.int32).at[tgt].add(
        jnp.where(keep_edge, sizes, 0))

    # reassemble the compacted global pin order: shard offsets from the
    # gathered live counts, then a write-once scatter psum (each global
    # slot is written by exactly one shard; integer adds are exact)
    live_now = pe_s != ghost_e
    lr = jnp.cumsum(live_now.astype(jnp.int32)) - 1
    cnts = jax.lax.all_gather(live_now.sum(), "model")
    offset = jnp.where(jnp.arange(S) < idx, cnts, 0).sum()
    p_new = cnts.sum()
    dest = jnp.where(live_now, offset + lr, p_pad)
    pv_out = jax.lax.psum(
        jnp.zeros(p_pad, jnp.int32).at[dest].add(
            jnp.where(live_now, pv_s, 0), mode="drop"), "model")
    pe_out = jax.lax.psum(
        jnp.zeros(p_pad, jnp.int32).at[dest].add(
            jnp.where(live_now, pe_s, 0), mode="drop"), "model")
    arange_p = jnp.arange(p_pad, dtype=jnp.int32)
    pv_out = jnp.where(arange_p < p_new, pv_out, ghost_v)
    pe_out = jnp.where(arange_p < p_new, pe_out, ghost_e)

    if ew_pop is None:
        return (new_vw, new_ew, new_es, m_new, pv_out, pe_out, p_new)

    # per-member weight rows ride the (replicated) structural edge map
    def _contract_row(w_row):
        gw_r = jnp.zeros(m_pad, jnp.float32).at[grp].add(
            jnp.where(alive_s, w_row[eo], 0.0))
        merged_r = jnp.where(keep_edge, gw_r[grp_of], 0.0)
        return jnp.zeros(m_pad, jnp.float32).at[tgt].add(
            jnp.where(keep_edge, merged_r, 0.0))

    ew_pop_new = jax.vmap(_contract_row)(ew_pop)
    return (new_vw, new_ew, new_es, m_new, pv_out, pe_out, p_new,
            ew_pop_new)


@lru_cache(maxsize=8)
def _contract_sharded_fn(mesh, has_pop: bool):
    """shard_map'd sharded contraction over ``mesh``'s "model" axis.
    Returns ``(coarse, p_new[, ew_pop_new])`` bit-equal to the global
    ``contract_arrays`` (asserted by ``tests/test_model_shard.py``)."""
    S = mesh.shape["model"]
    n_out = 8 if has_pop else 7

    def body(hga, cid, n_new, ew_pop):
        return _contract_sharded_body(hga, cid, n_new, ew_pop, S)

    in_specs = (_hga_model_pspecs(), P(), P(), P())
    sharded = shard_map(body, mesh, in_specs, (P(),) * n_out)

    def run(hga: HypergraphArrays, cid, n_new, ew_pop=None):
        out = sharded(hga, cid, n_new, ew_pop)
        new_vw, new_ew, new_es, m_new, pv, pe, p_new = out[:7]
        coarse = HypergraphArrays(
            pin_vertex=pv, pin_edge=pe, vertex_weights=new_vw,
            edge_weights=new_ew, edge_sizes=new_es,
            n=n_new, m=m_new, incident=None)
        if has_pop:
            return coarse, p_new, out[7]
        return coarse, p_new

    return run


def _match_round_impl(hga, part, key, c_max, max_stride: int,
                      max_edge_size: int):
    """Rating + matching only — the replicated front half of a model-
    sharded round (pair ratings are non-integer f32, so psum'd partials
    would break bit-identity; they stay replicated, DESIGN.md §15)."""
    lo, hi, rating = _pair_ratings(hga, part, max_stride=max_stride,
                                   max_edge_size=max_edge_size)
    cid, n_new = _mutual_match_dev(hga, lo, hi, rating, key, c_max)
    new_part = None
    if part is not None:
        new_part = jnp.zeros(hga.n_pad, jnp.int32).at[cid].max(part)
    return cid, n_new, new_part


_match_round = jax.jit(_match_round_impl,
                       static_argnames=("max_stride", "max_edge_size"))


@lru_cache(maxsize=8)
def _coarsen_round_model(mesh):
    """The coarsening round with the model-sharded contraction, as TWO
    dispatches: the replicated match jit, then the shard_map'd
    contraction.  They must not fuse into one jit — the shard_map's
    P("model") input constraint back-propagates through the shared pin
    operands and mis-partitions the replicated rating sort/scatters
    (observed to zero out the candidate ratings under GSPMD)."""
    contract_sh = jax.jit(_contract_sharded_fn(mesh, False))

    def run(hga, part, key, c_max, max_stride, max_edge_size):
        cid, n_new, new_part = _match_round(hga, part, key, c_max,
                                            max_stride=max_stride,
                                            max_edge_size=max_edge_size)
        coarse, p_new = contract_sh(hga, cid, n_new)
        return coarse, cid, new_part, p_new

    return run


def _model_mesh(model_shard: Optional[str]):
    """The ("pop", "model") mesh when the model-shard path is on and the
    model axis is real, else None (the replicated rounds)."""
    if popshard.resolve_model(model_shard) != "mesh":
        return None
    mesh = popshard.pop_mesh()
    return mesh if mesh.shape["model"] > 1 else None


def _round_can_shard(hga: HypergraphArrays, mesh, max_size: int) -> bool:
    """Per-level guard for the sharded contraction: the pin padding must
    split evenly over the model axis and every edge must fit inside one
    shard window (edge size <= p_loc, so owner rows + one halo always
    hold the whole edge)."""
    if mesh is None:
        return False
    S = mesh.shape["model"]
    p_loc = hga.p_pad // S
    return hga.p_pad % S == 0 and max_size <= p_loc


# --------------------------------------------------------------------------
# host-side schedule loop (readbacks: 3 scalars per round)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n_pad2", "m_pad2", "p_pad2"))
def _rebucket_jit(hga: HypergraphArrays, cid, part,
                  n_pad2: int, m_pad2: int, p_pad2: int):
    """Slice a freshly contracted level down to its own pow2 padding
    bucket (device-side; ghost ids remapped).  Keeps the per-level jit
    cache hot across levels and designs, exactly like the host path's
    ``arrays()`` bucketing."""
    ghost_v = jnp.int32(n_pad2 - 1)
    ghost_e = jnp.int32(m_pad2 - 1)
    pv = hga.pin_vertex[:p_pad2]
    pe = hga.pin_edge[:p_pad2]
    pv = jnp.where(pv >= hga.n, ghost_v, pv)
    pe = jnp.where(pe >= hga.m, ghost_e, pe)
    out = HypergraphArrays(
        pin_vertex=pv, pin_edge=pe,
        vertex_weights=hga.vertex_weights[:n_pad2],
        edge_weights=hga.edge_weights[:m_pad2],
        edge_sizes=hga.edge_sizes[:m_pad2],
        n=hga.n, m=hga.m, incident=None,
    )
    cid = jnp.where(cid >= hga.n, ghost_v, cid)
    part = None if part is None else part[:n_pad2]
    return out, cid, part


@partial(jax.jit, static_argnames=("d_pad",))
def _incidence_dev(hga: HypergraphArrays, d_pad: int) -> jnp.ndarray:
    """Dense [n_pad, d_pad] incident-edge layout (pad = -1) built on
    device — the coarse-level analogue of ``Hypergraph.incidence_matrix``
    so the Pallas gain kernels stay reachable without any host trip."""
    p_pad = hga.p_pad
    ghost_e = jnp.int32(hga.m_pad - 1)
    pv, pe = jax.lax.sort((hga.pin_vertex, hga.pin_edge), num_keys=2,
                          is_stable=False)
    arange_p = jnp.arange(p_pad, dtype=jnp.int32)
    first = jnp.full(hga.n_pad, p_pad, jnp.int32).at[pv].min(arange_p)
    col = arange_p - first[pv]
    live = pe != ghost_e
    row = jnp.where(live, pv, hga.n_pad - 1)
    col = jnp.where(live, col, d_pad)  # pushed out of bounds -> dropped
    return jnp.full((hga.n_pad, d_pad), -1, jnp.int32).at[
        row, col].set(pe, mode="drop")


def _attach_incident(hga: HypergraphArrays, m: int,
                     p: int) -> HypergraphArrays:
    """Attach the kernel gain layout when a kernel path is reachable,
    mirroring ``HypergraphArrays.from_host``'s policy (lane padding and
    the hub-vertex expansion guard)."""
    from repro.kernels import ops
    if not m or not ops.gain_layout_enabled():
        return hga
    deg = jnp.zeros(hga.n_pad, jnp.int32).at[hga.pin_vertex].add(
        (hga.pin_edge != hga.m_pad - 1).astype(jnp.int32))
    deg = deg.at[hga.n_pad - 1].set(0)
    d_max = _read_int(deg.max())  # one scalar readback, once per level
    d_pad = max(_round_pow2(max(d_max, 1), _INCIDENCE_LANE_PAD),
                _INCIDENCE_LANE_PAD)
    if hga.n_pad * d_pad > _INCIDENCE_MAX_EXPANSION * max(p, 1):
        return hga
    return dataclasses.replace(hga, incident=_incidence_dev(hga, d_pad))


def _read_int(x) -> int:
    """One device scalar read back to the host (a ``wait`` span, one
    ``readbacks``)."""
    with obs.span("wait"):
        v = int(x)
    obs.count("readbacks")
    return v


def device_coarsen(hg: Hypergraph, k: int, *,
                   contraction_limit_factor: int = 64, max_rounds: int = 64,
                   min_shrink: float = 0.02, seed: int = 0,
                   restrict_part=None,
                   max_cluster_frac: float = 1.0,
                   model_shard: Optional[str] = None) -> HierarchyArrays:
    """Build the multilevel hierarchy entirely on device.

    The host keeps only the round schedule (shared with the numpy
    coarsener via ``coarsen.round_schedule``): each round it reads back
    three scalars (n, m, live-pin count), decides done/stalled, and
    re-buckets the new level into its own pow2 padding so the jitted
    round and every downstream refinement dispatch hit their compile
    caches.  ``restrict_part`` projects through the levels on device —
    partition-aware hierarchies carry their partition with them.
    """
    sched = round_schedule(hg, k,
                           contraction_limit_factor=contraction_limit_factor,
                           max_rounds=max_rounds, min_shrink=min_shrink,
                           max_cluster_frac=max_cluster_frac)
    hga = hg.arrays()
    part = None
    if restrict_part is not None:
        pp = np.zeros(hga.n_pad, np.int32)
        pp[: hg.n] = np.asarray(restrict_part, np.int32)[: hg.n]
        part = jnp.asarray(pp)
    levels = [DeviceLevel(hga=hga, cluster_id=None, n=hg.n, m=hg.m,
                          p=hg.num_pins, part=part, host_hg=hg)]
    key = jax.random.PRNGKey(seed)
    mesh = _model_mesh(model_shard)
    cur, cur_part, n_cur = hga, part, hg.n
    for _ in range(sched.max_rounds):
        if sched.done(n_cur):
            break
        key, sub = jax.random.split(key)
        # the sharded contraction is bit-equal to the replicated one, so
        # levels it cannot take (odd padding split, an oversized edge)
        # just fall back round-by-round
        if mesh is not None and _round_can_shard(
                cur, mesh, _read_int(cur.edge_sizes.max())):
            round_fn = _coarsen_round_model(mesh)
        else:
            round_fn = _coarsen_round
        coarse, cid, new_part, p_new = round_fn(
            cur, cur_part, sub, jnp.float32(sched.c_max),
            max_stride=MAX_STRIDE, max_edge_size=MAX_EDGE_SIZE)
        n_new = _read_int(coarse.n)
        if sched.stalled(n_cur, n_new):
            break
        m_new, p_new = _read_int(coarse.m), _read_int(p_new)
        n_pad2 = _round_pow2(n_new + 1)
        m_pad2 = _round_pow2(m_new + 1)
        p_pad2 = _round_pow2(p_new + 1)
        if (n_pad2, m_pad2, p_pad2) != (coarse.n_pad, coarse.m_pad,
                                        coarse.p_pad):
            coarse, cid, new_part = _rebucket_jit(
                coarse, cid, new_part,
                n_pad2=n_pad2, m_pad2=m_pad2, p_pad2=p_pad2)
        coarse = _attach_incident(coarse, m_new, p_new)
        levels.append(DeviceLevel(hga=coarse, cluster_id=cid, n=n_new,
                                  m=m_new, p=p_new, part=new_part))
        cur, cur_part, n_cur = coarse, new_part, n_new
    return HierarchyArrays(levels=levels)


# --------------------------------------------------------------------------
# population-batched coarsening for the mutation cohort (DESIGN.md §10):
# one shared structure, alpha edge-weight rows, alpha partitions
# --------------------------------------------------------------------------
def _pair_ratings_population(hga: HypergraphArrays, parts: jnp.ndarray,
                             ew_pop: jnp.ndarray, *, max_stride: int,
                             max_edge_size: int, batch: bool):
    """Per-member aggregated, weight-normalised heavy-edge ratings over
    ONE shared candidate structure.

    ``parts`` [alpha, n_pad] restricts candidates to pairs that are
    same-block in EVERY member (the intersection of the per-member
    partition-aware restrictions — the invariant that lets one hierarchy
    serve the whole cohort with every member's cut projecting exactly).
    ``ew_pop`` [alpha, m_pad] are the per-member reweighted edge weights.
    Returns ``(lo, hi, rating_pop)`` with ``rating_pop`` [alpha, C].

    ``batch`` picks how the per-member segment sums dispatch: one
    batched call (``rating_segment_sum_batch``) or a per-member loop of
    scalar calls — the ``REPRO_MUTATE_PATH=loop`` reference.  Both give
    bit-identical rows (the sort permutation is stable and shared, and
    each aggregation path adds in the same order per member).
    """
    from repro.kernels import ops
    n_pad = hga.n_pad
    alpha = parts.shape[0]
    ghost_v = jnp.int32(n_pad - 1)
    sizes = hga.edge_sizes
    unit_pop = jnp.where(sizes[None, :] > 1,
                         ew_pop / jnp.maximum(sizes - 1, 1)[None, :], 0.0)
    u, v, valid, pe_cat = _stride_candidates(
        hga, max_stride=max_stride, max_edge_size=max_edge_size)
    valid = valid & (parts[:, u] == parts[:, v]).all(axis=0)
    lo = jnp.where(valid, jnp.minimum(u, v), ghost_v)
    hi = jnp.where(valid, jnp.maximum(u, v), ghost_v)
    r_pop = jnp.where(valid[None, :], unit_pop[:, pe_cat], 0.0)  # [alpha, C]

    # make duplicate pairs adjacent; a STABLE key sort yields one
    # permutation shared by every member's value row (and by both the
    # batch and loop dispatch paths), so per-member aggregation order —
    # hence every f32 sum — is identical across paths
    c = lo.shape[0]
    lo, hi, perm = jax.lax.sort(
        (lo, hi, jnp.arange(c, dtype=jnp.int32)), num_keys=2,
        is_stable=True)
    r_pop = r_pop[:, perm]
    newg = jnp.ones(c, bool).at[1:].set(
        (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1]))
    seg = (jnp.cumsum(newg.astype(jnp.int32)) - 1).astype(jnp.int32)
    if batch:
        agg_pop = ops.rating_segment_sum_batch(r_pop, seg, c)
    else:  # the per-member reference loop (alpha scalar dispatches)
        agg_pop = jnp.stack([ops.rating_segment_sum(r_pop[a], seg, c)
                             for a in range(alpha)])

    lo_g = jnp.full(c, ghost_v, jnp.int32).at[seg].min(lo)
    hi_g = jnp.full(c, ghost_v, jnp.int32).at[seg].min(hi)
    cw = hga.vertex_weights
    agg_pop = agg_pop / jnp.maximum(cw[lo_g] * cw[hi_g], 1e-12)[None, :]
    return lo_g, hi_g, agg_pop


def _coarsen_round_population_impl(hga: HypergraphArrays, parts, ew_pop,
                                   key, c_max, max_stride: int,
                                   max_edge_size: int, batch: bool):
    """One cohort coarsening round: batched rating, consensus matching
    (summed member ratings — degenerates to the member's own rating for
    a cohort of one), shared contraction carrying every weight row."""
    lo, hi, rating_pop = _pair_ratings_population(
        hga, parts, ew_pop, max_stride=max_stride,
        max_edge_size=max_edge_size, batch=batch)
    cid, n_new = _mutual_match_dev(hga, lo, hi, rating_pop.sum(axis=0),
                                   key, c_max)
    coarse, p_new, ew_new = contract_arrays(hga, cid, n_new, ew_pop=ew_pop)
    # block of each cluster = block of any member (same by construction:
    # the candidate restriction required agreement in every member)
    new_parts = jax.vmap(
        lambda p: jnp.zeros(hga.n_pad, jnp.int32).at[cid].max(p))(parts)
    return coarse, cid, new_parts, ew_new, p_new


_coarsen_round_population = jax.jit(
    _coarsen_round_population_impl,
    static_argnames=("max_stride", "max_edge_size", "batch"))


def _match_round_population_impl(hga, parts, ew_pop, key, c_max,
                                 max_stride: int, max_edge_size: int,
                                 batch: bool):
    """Cohort rating + consensus matching — the replicated front half of
    a model-sharded population round (see ``_match_round_impl``)."""
    lo, hi, rating_pop = _pair_ratings_population(
        hga, parts, ew_pop, max_stride=max_stride,
        max_edge_size=max_edge_size, batch=batch)
    cid, n_new = _mutual_match_dev(hga, lo, hi, rating_pop.sum(axis=0),
                                   key, c_max)
    new_parts = jax.vmap(
        lambda p: jnp.zeros(hga.n_pad, jnp.int32).at[cid].max(p))(parts)
    return cid, n_new, new_parts


_match_round_population = jax.jit(
    _match_round_population_impl,
    static_argnames=("max_stride", "max_edge_size", "batch"))


@lru_cache(maxsize=8)
def _coarsen_round_population_model(mesh):
    """Cohort coarsening round with the model-sharded contraction —
    two dispatches for the same reason as ``_coarsen_round_model``;
    every member's weight row rides the replicated edge map inside the
    shard_map."""
    contract_sh = jax.jit(_contract_sharded_fn(mesh, True))

    def run(hga, parts, ew_pop, key, c_max, max_stride, max_edge_size,
            batch):
        cid, n_new, new_parts = _match_round_population(
            hga, parts, ew_pop, key, c_max, max_stride=max_stride,
            max_edge_size=max_edge_size, batch=batch)
        coarse, p_new, ew_new = contract_sh(hga, cid, n_new, ew_pop)
        return coarse, cid, new_parts, ew_new, p_new

    return run


@partial(jax.jit, static_argnames=("n_pad2", "m_pad2", "p_pad2"))
def _rebucket_pop_jit(hga: HypergraphArrays, cid, parts, ew_pop,
                      n_pad2: int, m_pad2: int, p_pad2: int):
    """Population analogue of ``_rebucket_jit``: slice the shared
    structure AND the alpha-carried leaves down to the level's own pow2
    bucket."""
    out, cid, _ = _rebucket_jit(hga, cid, None, n_pad2=n_pad2,
                                m_pad2=m_pad2, p_pad2=p_pad2)
    return out, cid, parts[:, :n_pad2], ew_pop[:, :m_pad2]


@dataclasses.dataclass
class PopulationLevel:
    """One shared-structure cohort level: broadcast structure (``hga``,
    ``cluster_id``) plus the alpha-carried leaves (``ew_pop`` per-member
    edge weights, ``parts`` per-member projected partitions)."""
    hga: HypergraphArrays
    cluster_id: Optional[jnp.ndarray]
    ew_pop: jnp.ndarray            # [alpha, m_pad]
    parts: jnp.ndarray             # [alpha, n_pad]
    n: int
    m: int
    p: int


@dataclasses.dataclass
class PopulationHierarchy:
    """Shared-structure multilevel hierarchy for the mutation cohort.

    The narrow population analogue of the hierarchy protocol: one
    structure per level (broadcast), per-member edge weights and
    partitions stacked on a leading alpha axis."""
    levels: List["PopulationLevel"]

    @property
    def num_levels(self) -> int:
        return len(self.levels)

    def sizes(self) -> List[int]:
        return [lv.n for lv in self.levels]

    def level_n(self, li: int) -> int:
        return self.levels[li].n

    def level_arrays(self, li: int) -> HypergraphArrays:
        return self.levels[li].hga

    def level_ew(self, li: int) -> jnp.ndarray:
        return self.levels[li].ew_pop

    def level_parts(self, li: int) -> jnp.ndarray:
        return self.levels[li].parts

    def project_pop(self, parts, li: int) -> jnp.ndarray:
        """Project the cohort at level ``li`` onto level ``li - 1`` on
        device (same gather ``HierarchyArrays.project_pop`` does)."""
        lv = self.levels[li]
        parts = jnp.asarray(parts, jnp.int32)
        n_pad = lv.hga.n_pad
        if parts.shape[1] < n_pad:
            pad = jnp.zeros((parts.shape[0], n_pad - parts.shape[1]),
                            jnp.int32)
            parts = jnp.concatenate([parts, pad], axis=1)
        return jnp.take(parts, lv.cluster_id, axis=1)


def population_coarsen(hg: Hypergraph, parts, ew_pop, k: int, *,
                       contraction_limit_factor: int = 64,
                       max_rounds: int = 64, min_shrink: float = 0.02,
                       seed: int = 0, max_cluster_frac: float = 1.0,
                       batch: bool = True,
                       model_shard: Optional[str] = None
                       ) -> PopulationHierarchy:
    """Build ONE partition-aware hierarchy for the whole mutation cohort.

    ``parts`` [alpha, n] warm-start partitions, ``ew_pop`` [alpha, m]
    per-member reweighted edge weights — both over ``hg``'s structure.
    The schedule is the shared ``coarsen.round_schedule`` (it reads only
    vertex weights and sizes, identical for every member), the matching
    is one consensus matching per round, and every level's structure is
    born once and broadcast: only the weight/partition leaves carry the
    alpha axis.  ``batch=False`` dispatches the per-member rating
    aggregation as a loop of scalar calls (the ``REPRO_MUTATE_PATH=loop``
    reference) — the resulting hierarchy is bit-identical either way.
    """
    sched = round_schedule(hg, k,
                           contraction_limit_factor=contraction_limit_factor,
                           max_rounds=max_rounds, min_shrink=min_shrink,
                           max_cluster_frac=max_cluster_frac)
    hga = hg.arrays()
    alpha = len(parts)
    pp = np.zeros((alpha, hga.n_pad), np.int32)
    pp[:, : hg.n] = np.asarray(parts, np.int32)[:, : hg.n]
    parts = jnp.asarray(pp)
    ww = np.zeros((alpha, hga.m_pad), np.float32)
    ww[:, : hg.m] = np.asarray(ew_pop, np.float32)[:, : hg.m]
    ew_pop = jnp.asarray(ww)

    levels = [PopulationLevel(hga=hga, cluster_id=None, ew_pop=ew_pop,
                              parts=parts, n=hg.n, m=hg.m, p=hg.num_pins)]
    key = jax.random.PRNGKey(seed)
    mesh = _model_mesh(model_shard)
    cur, cur_parts, cur_ew, n_cur = hga, parts, ew_pop, hg.n
    for _ in range(sched.max_rounds):
        if sched.done(n_cur):
            break
        key, sub = jax.random.split(key)
        if mesh is not None and _round_can_shard(
                cur, mesh, int(cur.edge_sizes.max())):
            round_fn = _coarsen_round_population_model(mesh)
        else:
            round_fn = _coarsen_round_population
        coarse, cid, new_parts, new_ew, p_new = round_fn(
            cur, cur_parts, cur_ew, sub, jnp.float32(sched.c_max),
            max_stride=MAX_STRIDE, max_edge_size=MAX_EDGE_SIZE, batch=batch)
        n_new = int(coarse.n)
        if sched.stalled(n_cur, n_new):
            break
        m_new, p_new = int(coarse.m), int(p_new)
        n_pad2 = _round_pow2(n_new + 1)
        m_pad2 = _round_pow2(m_new + 1)
        p_pad2 = _round_pow2(p_new + 1)
        if (n_pad2, m_pad2, p_pad2) != (coarse.n_pad, coarse.m_pad,
                                        coarse.p_pad):
            coarse, cid, new_parts, new_ew = _rebucket_pop_jit(
                coarse, cid, new_parts, new_ew,
                n_pad2=n_pad2, m_pad2=m_pad2, p_pad2=p_pad2)
        coarse = _attach_incident(coarse, m_new, p_new)
        levels.append(PopulationLevel(hga=coarse, cluster_id=cid,
                                      ew_pop=new_ew, parts=new_parts,
                                      n=n_new, m=m_new, p=p_new))
        cur, cur_parts, cur_ew, n_cur = coarse, new_parts, new_ew, n_new
    return PopulationHierarchy(levels=levels)
