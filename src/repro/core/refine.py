"""Refinement: two-tier scheme (DESIGN.md §3).

* ``lp_refine`` — balanced label-propagation sweeps.  Every vertex scores
  all k destination blocks at once (vectorised gain matrix), proposals are
  accepted in global gain order subject to per-block capacity, computed
  with sorted prefix sums — no sequential loop.  Used on large/fine levels.
* ``fm_refine`` — classic one-move-at-a-time FM with negative-gain
  hill-climbing and best-prefix rollback, expressed as a ``lax.scan``.
  Used on coarse levels (small n) where move quality matters most.
  Each move's gains come from segment-sums over the pins or from one
  contraction of the level's dense incidence (``fm_gain_route``).

The population LP tier is device-resident: the whole 5-attempt
frac-halving acceptance loop of a round runs inside one jitted
``lax.while_loop`` (``_lp_attempt_population``), so ``lp_refine_population``
performs ONE dispatch plus one small readback (cuts + improved flags) per
round instead of up to 10 blocking round-trips.  Per-member trajectories
stay bit-identical to the scalar ``lp_refine`` host loop on
integer-weight instances.

Both population tiers route through the ``REPRO_POP_SHARD`` dispatcher
(``core/popshard.py``, DESIGN.md §11): on the ``mesh`` path (auto when
>1 device) each pass/attempt loop is shard_map'd over the
("pop", "model") mesh with structure replicated and member rows sharded
over "pop" — per-member results identical to the other paths.

Both tiers guarantee: the returned partition never violates the balance
cap and never has a larger cut than the input.
"""
from __future__ import annotations

import dataclasses
import weakref
from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import obs
from ..jaxcompat import shard_map
from .hypergraph import HypergraphArrays
from . import metrics
from . import popshard

NEG = -1e30


def pad_part(part, n_pad: int) -> jnp.ndarray:
    """Pad a length-n partition vector to n_pad (pad block = 0; padded
    vertices have zero weight and no pins, so the value is inert)."""
    part = jnp.asarray(part, jnp.int32)
    if part.shape[0] == n_pad:
        return part
    return jnp.concatenate(
        [part, jnp.zeros(n_pad - part.shape[0], jnp.int32)])


def pad_parts(parts, n_pad: int) -> jnp.ndarray:
    """Stack a population (list of [n] vectors or an [alpha, n] array)
    into a padded [alpha, n_pad] tensor."""
    if isinstance(parts, (list, tuple)):
        return jnp.stack([pad_part(p, n_pad) for p in parts])
    parts = jnp.asarray(parts, jnp.int32)
    if parts.ndim != 2:
        raise ValueError(f"expected [alpha, n] population, got {parts.shape}")
    if parts.shape[1] == n_pad:
        return parts
    pad = jnp.zeros((parts.shape[0], n_pad - parts.shape[1]), jnp.int32)
    return jnp.concatenate([parts, pad], axis=1)


# --------------------------------------------------------------------------
# label propagation round (jitted)
# --------------------------------------------------------------------------
def accept_moves(part: jnp.ndarray, target: jnp.ndarray, gain: jnp.ndarray,
                 propose: jnp.ndarray, vertex_weights: jnp.ndarray,
                 bw: jnp.ndarray, cap: jnp.ndarray, frac: jnp.ndarray,
                 k: int, incumbent: jnp.ndarray | None = None,
                 mig_remaining: jnp.ndarray | None = None) -> jnp.ndarray:
    """Balanced parallel-move acceptance (shared by lp_round and the
    distributed population step).

    Proposals (vertex -> target block, expected gain) are ranked by gain;
    the top ``frac`` are kept; per-target-block capacity is enforced with
    a prefix sum over the sorted proposal weights — no sequential loop.

    ``incumbent`` + ``mig_remaining`` (optional, DESIGN.md §14) add the
    bounded-migration objective: ``mig_remaining`` is the moved-vertex
    weight still allowed relative to ``incumbent``.  A second prefix sum
    over the sorted order accumulates the POSITIVE migration deltas of
    the kept proposals; a migration-increasing proposal is accepted only
    while that conservative cumulative stays within the remaining
    budget (rejected earlier proposals only make it safer), and
    migration-decreasing proposals are always migration-feasible.  With
    an infinite budget every mask is all-True, so unconstrained
    trajectories are bit-identical to the constrained trace.
    """
    n_pad = part.shape[0]
    order = jnp.argsort(jnp.where(propose, -gain, -NEG))
    ranks = jnp.zeros(n_pad, jnp.int32).at[order].set(
        jnp.arange(n_pad, dtype=jnp.int32))
    keep_n = jnp.ceil(frac * propose.sum()).astype(jnp.int32)
    propose = propose & (ranks < keep_n)

    w_sorted = jnp.where(propose, vertex_weights, 0.0)[order]
    tgt_sorted = jnp.where(propose, target, k)[order]  # k = "no move"
    tgt_oh = jax.nn.one_hot(tgt_sorted, k + 1, dtype=w_sorted.dtype)
    pref = jnp.cumsum(tgt_oh * w_sorted[:, None], axis=0)    # [n_pad, k+1]
    fits_sorted = (pref[:, :k] <= (cap - bw)[None, :] + 1e-6)
    fit_own = jnp.take_along_axis(
        fits_sorted, jnp.minimum(tgt_sorted, k - 1)[:, None], axis=-1)[:, 0]
    accept_sorted = fit_own & (tgt_sorted < k)
    if incumbent is not None:
        moved_now = (part != incumbent).astype(vertex_weights.dtype)
        moved_tgt = (target != incumbent).astype(vertex_weights.dtype)
        delta = vertex_weights * (moved_tgt - moved_now)
        delta_sorted = jnp.where(propose, delta, 0.0)[order]
        pos_pref = jnp.cumsum(jnp.maximum(delta_sorted, 0.0))
        mig_ok = (delta_sorted <= 0.0) | (pos_pref <= mig_remaining + 1e-6)
        accept_sorted = accept_sorted & mig_ok
    accept = jnp.zeros(n_pad, bool).at[order].set(accept_sorted)
    return jnp.where(accept, target, part)


def _with_weights(hga: HypergraphArrays,
                  edge_weight_override: jnp.ndarray | None
                  ) -> HypergraphArrays:
    if edge_weight_override is None:
        return hga
    return dataclasses.replace(hga, edge_weights=edge_weight_override)


def _lp_round_from_gains(h: HypergraphArrays, part: jnp.ndarray, k: int,
                         cap: jnp.ndarray, frac: jnp.ndarray,
                         gains: jnp.ndarray,
                         k_live: jnp.ndarray | None = None,
                         incumbent: jnp.ndarray | None = None,
                         mig_budget: jnp.ndarray | None = None
                         ) -> jnp.ndarray:
    """Proposal + balanced acceptance given a precomputed gain matrix
    (the gain assembly is hoisted out so population callers can route it
    through the batched kernels instead of vmapping a pallas_call).

    ``k_live`` (optional traced scalar, instance axis, DESIGN.md §12):
    blocks ``j >= k_live`` are masked to NEG so a k_live-way instance
    refined inside a k-padded bucket proposes exactly the moves a solo
    k=k_live run would — columns below k_live are untouched and argmax
    tie-breaking over the row-major flat order is preserved, so the
    trajectory is bit-identical.

    ``incumbent`` [n_pad] + ``mig_budget`` (optional traced scalar,
    DESIGN.md §14): bound the total moved-vertex weight relative to the
    incumbent assignment.  The remaining budget for this round is the
    full budget minus what the current partition has already migrated.
    """
    n_pad = h.n_pad
    own = jax.nn.one_hot(part, k, dtype=bool)
    gains = jnp.where(own, NEG, gains)
    if k_live is not None:
        gains = jnp.where(jnp.arange(k)[None, :] >= k_live, NEG, gains)
    best_j = jnp.argmax(gains, axis=-1).astype(jnp.int32)
    best_g = jnp.take_along_axis(gains, best_j[:, None], axis=-1)[:, 0]

    valid = (jnp.arange(n_pad) < h.n) & (h.vertex_weights > 0)
    propose = valid & (best_g > 1e-9)
    bw = metrics.block_weights(h, part, k)
    mig_remaining = None
    if incumbent is not None:
        moved = jnp.where(part != incumbent, h.vertex_weights, 0.0).sum()
        mig_remaining = mig_budget - moved
    return accept_moves(part, best_j, best_g, propose, h.vertex_weights,
                        bw, cap, frac, k, incumbent=incumbent,
                        mig_remaining=mig_remaining)


def _lp_round_impl(hga: HypergraphArrays, part: jnp.ndarray, k: int,
                   cap: jnp.ndarray, frac: jnp.ndarray,
                   edge_weight_override: jnp.ndarray | None = None
                   ) -> jnp.ndarray:
    """lp_round body (unjitted; shared by the scalar and the population
    entry points)."""
    h = _with_weights(hga, edge_weight_override)
    gains = metrics.gain_matrix(h, part, k)                   # [n_pad, k]
    return _lp_round_from_gains(h, part, k, cap, frac, gains)


@partial(jax.jit, static_argnames=("k",))
def lp_round(hga: HypergraphArrays, part: jnp.ndarray, k: int,
             cap: jnp.ndarray, frac: jnp.ndarray,
             edge_weight_override: jnp.ndarray | None = None
             ) -> jnp.ndarray:
    """One parallel move round; returns the new partition.

    ``frac`` in (0,1]: accept only the top fraction of positive-gain
    proposals (the host halves it on conflict-induced regressions).
    ``edge_weight_override`` lets mutation bias gains without touching the
    real weights.
    """
    return _lp_round_impl(hga, part, k, cap, frac, edge_weight_override)


def _lp_round_population_impl(hga: HypergraphArrays, parts: jnp.ndarray,
                              k: int, cap: jnp.ndarray, fracs: jnp.ndarray,
                              edge_weight_override: jnp.ndarray | None = None,
                              edge_weights_pop: jnp.ndarray | None = None,
                              k_live: jnp.ndarray | None = None,
                              incumbent: jnp.ndarray | None = None,
                              mig_budget: jnp.ndarray | None = None,
                              pin_axis: str | None = None
                              ) -> jnp.ndarray:
    """lp_round for all members: gains come from the batched dispatcher
    (one kernel launch for the population), the proposal/acceptance tail
    is vmapped — per-lane ops identical to the scalar round.

    ``edge_weights_pop`` [alpha, m_pad] gives each member its OWN edge
    weights over the shared structure (the mutation cohort, DESIGN.md
    §10); ``edge_weight_override`` [m_pad] stays the shared-bias variant.
    ``incumbent`` [n_pad] + ``mig_budget`` scalar are shared by all
    members (every lane bounds its own migration, DESIGN.md §14).
    ``pin_axis``: pin tables row-sharded over that mesh axis — the gain
    matrices arrive as psum'd partials, bit-equal to the replicated
    assembly (DESIGN.md §15); the acceptance tail below runs on
    replicated [n_pad]-indexed values and is untouched.
    """
    h = _with_weights(hga, edge_weight_override)
    gains = metrics._gain_matrix_population_impl(
        h, parts, k, ew_pop=edge_weights_pop, pin_axis=pin_axis)
    return jax.vmap(
        lambda p, f, g: _lp_round_from_gains(h, p, k, cap, f, g,
                                             k_live=k_live,
                                             incumbent=incumbent,
                                             mig_budget=mig_budget))(
            parts, fracs, gains)


@partial(jax.jit, static_argnames=("k",))
def lp_round_population(hga: HypergraphArrays, parts: jnp.ndarray, k: int,
                        cap: jnp.ndarray, fracs: jnp.ndarray,
                        edge_weight_override: jnp.ndarray | None = None
                        ) -> jnp.ndarray:
    """One parallel move round for ALL population members in a single
    dispatch.  ``parts`` [alpha, n_pad]; ``fracs`` [alpha] per-member
    acceptance fraction (the host anneals them independently)."""
    return _lp_round_population_impl(hga, parts, k, cap, fracs,
                                     edge_weight_override)


def _lp_attempt_population_impl(hga: HypergraphArrays, parts: jnp.ndarray,
                                cuts: jnp.ndarray, fracs: jnp.ndarray,
                                attempts: jnp.ndarray, k: int,
                                cap: jnp.ndarray,
                                edge_weight_override=None,
                                edge_weights_pop=None,
                                pop_axis: str | None = None,
                                live: jnp.ndarray | None = None,
                                k_live: jnp.ndarray | None = None,
                                incumbent: jnp.ndarray | None = None,
                                mig_budget: jnp.ndarray | None = None,
                                pin_axis: str | None = None):
    """Device-resident LP attempt loop fused into one ``lax.while_loop``.

    Per member (mirroring the scalar ``lp_refine`` inner loop exactly):
    propose a round at the current acceptance fraction, measure the cut
    on the TRUE edge weights, accept on improvement, otherwise quarter
    the fraction and retry.  The loop spins on-device while NO lane
    improves (the case that used to cost 2 blocking dispatches per
    attempt); once any lane improves — typically all of them, on the
    first attempt — it returns so the host can drop the improved lanes
    from the batch and resume the stragglers in a smaller shape bucket
    with the remaining ``attempts`` (a traced scalar, so bucket size is
    the only thing that retraces).

    ``pop_axis``: when the batch is sharded over a mesh axis (the
    ``REPRO_POP_SHARD=mesh`` path, DESIGN.md §11), the only cross-member
    quantity — the "did any lane improve" loop flag — is psum'd over that
    axis, so every shard runs the exact trip count the single-device
    batch would.  It is carried through the loop state (computed in the
    body) so the cond stays collective-free.

    ``live`` (optional [alpha] bool, instance axis, DESIGN.md §12): lanes
    with ``live=False`` never accept (their parts/cuts pass through
    unchanged and they cannot raise the improvement flag).  The instance
    tier uses this to freeze already-improved or converged lanes in
    place instead of compacting them out of the dispatch — per-lane
    trajectories are invariant to which other lanes share the batch, so
    the results are identical to the compacted host loop.

    ``k_live`` (optional traced scalar): see ``_lp_round_from_gains``.

    Returns ``(parts, cuts, improved, fracs, used)``; cuts are f32
    (bit-identical trajectories are guaranteed on integer-weight
    instances, as in the host loop this replaces).
    """
    def cond(carry):
        _, _, _, _, any_improved, t = carry
        return (t < attempts) & ~any_improved

    def body(carry):
        parts, cuts, fracs, improved, _, t = carry
        cands = _lp_round_population_impl(hga, parts, k, cap, fracs,
                                          edge_weight_override,
                                          edge_weights_pop,
                                          k_live=k_live,
                                          incumbent=incumbent,
                                          mig_budget=mig_budget,
                                          pin_axis=pin_axis)
        if edge_weights_pop is None:
            cs = jax.vmap(
                lambda p: metrics.cutsize(hga, p, k,
                                          pin_axis=pin_axis))(cands)
        else:  # each member's acceptance cut on its own reweight
            cs = metrics._cutsize_population_weighted_impl(
                hga, cands, edge_weights_pop, k, pin_axis=pin_axis)
        take = cs < cuts - 1e-6
        if live is not None:
            take = take & live
        parts = jnp.where(take[:, None], cands, parts)
        cuts = jnp.where(take, cs, cuts)
        fracs = jnp.where(take, fracs, fracs * 0.25)
        improved = improved | take
        any_improved = improved.any()
        if pop_axis is not None:
            any_improved = jax.lax.psum(
                any_improved.astype(jnp.int32), pop_axis) > 0
        return parts, cuts, fracs, improved, any_improved, t + 1

    init = (parts, cuts, fracs, jnp.zeros(parts.shape[0], bool),
            jnp.bool_(False), jnp.int32(0))
    parts, cuts, fracs, improved, _, used = jax.lax.while_loop(cond, body,
                                                               init)
    return parts, cuts, improved, fracs, used


_lp_attempt_population = partial(jax.jit, static_argnames=("k",))(
    _lp_attempt_population_impl)


def _hga_specs(model: bool):
    """shard_map spec (sub)tree for a HypergraphArrays argument: fully
    replicated, or — on the model-shard path (DESIGN.md §15) — pin
    tables row-sharded over "model" with every edge/vertex-indexed leaf
    replicated.  The model placement drops the incidence layout, so the
    spec tree's structure matches (incident=None)."""
    if not model:
        return P()
    return HypergraphArrays(pin_vertex=P("model"), pin_edge=P("model"),
                            vertex_weights=P(), edge_weights=P(),
                            edge_sizes=P(), n=P(), m=P(), incident=None)


@lru_cache(maxsize=32)
def _lp_attempt_population_mesh(mesh, k: int, model: bool = False):
    """The fused LP attempt loop shard_map'd over the ("pop", "model")
    mesh: partition/cut/frac/weight-row leaves sharded over "pop";
    structure replicated, or — with ``model`` (``REPRO_MODEL_SHARD=mesh``,
    DESIGN.md §15) — pin tables row-sharded over "model" with the
    pin-indexed reductions psum'd.  Cached per (mesh, k, model); jit
    handles the rest of the signature (presence of the optional weight
    args, bucket shapes).
    """
    def body(hga, parts, cuts, fracs, attempts, cap, ewo, ew_pop,
             incumbent, mig_budget):
        return _lp_attempt_population_impl(
            hga, parts, cuts, fracs, attempts, k, cap,
            edge_weight_override=ewo, edge_weights_pop=ew_pop,
            pop_axis="pop", incumbent=incumbent, mig_budget=mig_budget,
            pin_axis="model" if model else None)

    fn = shard_map(
        body, mesh,
        in_specs=(_hga_specs(model), P("pop"), P("pop"), P("pop"), P(),
                  P(), P(), P("pop"), P(), P()),
        out_specs=(P("pop"), P("pop"), P("pop"), P("pop"), P()))
    return jax.jit(fn)




def lp_refine(hga: HypergraphArrays, part: np.ndarray, k: int, eps: float,
              max_iters: int = 24, patience: int = 3,
              edge_weight_override=None) -> Tuple[np.ndarray, float]:
    """Host loop around ``lp_round`` with regression-safe acceptance."""
    cap = metrics.balance_cap(hga.total_weight, k, eps)
    part = pad_part(part, hga.n_pad)
    cut = float(metrics.cutsize_jit(hga, part, k))
    stall = 0
    for _ in range(max_iters):
        frac = 1.0
        improved = False
        for _attempt in range(5):
            cand = lp_round(hga, part, k, cap, jnp.float32(frac),
                            edge_weight_override)
            c = float(metrics.cutsize_jit(hga, cand, k))
            if c < cut - 1e-6:
                part, cut, improved = cand, c, True
                break
            frac *= 0.25
        if not improved:
            stall += 1
            if stall >= patience:
                break
        else:
            stall = 0
    return np.asarray(part), cut


def lp_refine_population(hga: HypergraphArrays, parts, k: int, eps: float,
                         max_iters: int = 24, patience: int = 3,
                         edge_weight_override=None, edge_weights_pop=None,
                         shard: str | None = None,
                         incumbent=None, mig_budget: float | None = None,
                         model_shard: str | None = None
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched ``lp_refine``: ONE device dispatch per round covers the
    whole population, attempts included.

    The per-round acceptance loop (5 frac-halving attempts + cut
    evaluation) runs on-device inside ``_lp_attempt_population``; the
    host only tracks stall counters and convergence per member, so each
    member follows exactly the trajectory the scalar ``lp_refine`` would
    give it — the batched and looped paths agree bit-for-bit on
    integer-weight instances.
    Returns (parts [alpha, n_pad], cuts [alpha]).

    ``edge_weights_pop`` [alpha, m_pad]: per-member edge weights over the
    shared structure (the mutation cohort, DESIGN.md §10) — each member's
    gains AND acceptance cuts use its own row, exactly as if it refined
    its own reweighted hypergraph.

    ``shard`` (None = ``REPRO_POP_SHARD``): on the ``mesh`` path the
    attempt loop runs shard_map'd over the ("pop", "model") mesh
    (DESIGN.md §11) — structure replicated, member rows sharded over
    "pop", trip counts synchronised by a psum'd improvement flag — with
    per-member trajectories bit-identical to the single-device engine.

    ``incumbent`` [n] + ``mig_budget`` (optional, DESIGN.md §14): every
    member's moved-vertex weight relative to the incumbent stays within
    the budget throughout refinement (an infinite budget is bit-identical
    to omitting both).

    ``model_shard`` (None = ``REPRO_MODEL_SHARD``, DESIGN.md §15): on the
    mesh path, "mesh" additionally row-shards the pin tables over the
    mesh's "model" axis (>1) with the pin-indexed segment-sums psum'd —
    for instances whose pin arrays outgrow one device — still bit-equal
    to the replicated engine.
    """
    cap = _cap_for(hga, k, eps)
    parts = pad_parts(parts, hga.n_pad)
    alpha = parts.shape[0]
    inc = mb = None
    if incumbent is not None:
        inc = pad_part(incumbent, hga.n_pad)
        mb = float(np.inf if mig_budget is None else mig_budget)
    if edge_weights_pop is not None:
        edge_weights_pop = jnp.asarray(edge_weights_pop, jnp.float32)
        cuts = np.asarray(metrics.cutsize_population_weighted(
            hga, parts, edge_weights_pop, k), np.float64)
    else:
        cuts = np.asarray(metrics.cutsize_population(hga, parts, k),
                          np.float64)

    mesh_fn = ewo_m = None
    if popshard.resolve(shard) == "mesh" and alpha > 1:
        mesh, npop, pop_sh, hga_m, cap_m, model = _mesh_dispatch(
            hga, k, eps, model_shard)
        mesh_fn = _lp_attempt_population_mesh(mesh, k, model)
        if edge_weight_override is not None:
            ewo_m = jax.device_put(edge_weight_override,
                                   popshard.replicated(mesh))
        if inc is not None:
            inc = jax.device_put(inc, popshard.replicated(mesh))
        # host mirror (the FM tier's design): active rows merge with
        # numpy writes, never through a single-device detour
        parts = np.array(parts)
        if edge_weights_pop is not None:
            edge_weights_pop = np.asarray(edge_weights_pop)
    else:
        # replicated structure on every device this path touches
        popshard.enforce_structure_budget(hga, 1)

    stall = np.zeros(alpha, np.int32)
    done = np.zeros(alpha, bool)
    for _ in range(max_iters):
        active = np.nonzero(~done)[0]
        if len(active) == 0:
            break
        # compact to the active subpopulation: converged members cost
        # nothing, mirroring the scalar loop's early exits (per-member
        # trajectories are unchanged).  Each distinct active count traces
        # once — bounded by alpha, paid once per padded-shape bucket,
        # then pure hot-path savings.  Within a round, the fused attempt
        # loop is ONE dispatch per shape bucket: the device loop spins
        # through no-improvement attempts itself and returns when lanes
        # improve (usually attempt 1, usually all of them); only
        # stragglers re-dispatch in a smaller bucket with the leftover
        # attempt budget.  The only data read back per dispatch are the
        # [active]-sized cuts / improved / fracs vectors (plus, on the
        # mesh path, the active partition rows — it compacts through a
        # host mirror, like the FM tier).
        improved_round = np.zeros(alpha, bool)
        idx = active
        fracs = np.ones(alpha, np.float32)
        remaining = 5
        while remaining > 0 and len(idx):
            # bucket slicing works on both mirrors (np parts on the mesh
            # path, jnp parts otherwise — jnp accepts the numpy index)
            sub = parts[idx] if len(idx) < alpha else parts
            sub_ew = None
            if edge_weights_pop is not None:
                sub_ew = (edge_weights_pop[idx] if len(idx) < alpha
                          else edge_weights_pop)
            if mesh_fn is not None:
                # mesh dispatch: pad the bucket to the pop-axis size
                # (pad lanes mirror row 0, so results and the psum'd
                # improvement flag are unchanged), shard rows over "pop";
                # read back the active rows into the host mirror
                na = len(idx)
                new_sub, new_cuts, improved, new_fracs, used = mesh_fn(
                    hga_m,
                    _put_rows(sub, npop, pop_sh),
                    _put_rows(np.asarray(cuts[idx], np.float32), npop,
                              pop_sh),
                    _put_rows(fracs[idx], npop, pop_sh),
                    jnp.int32(remaining), cap_m, ewo_m,
                    None if sub_ew is None
                    else _put_rows(sub_ew, npop, pop_sh),
                    inc, mb)
                parts[idx] = np.asarray(new_sub)[:na]
                new_cuts = np.asarray(new_cuts)[:na]
                improved = np.asarray(improved)[:na]
                new_fracs = np.asarray(new_fracs)[:na]
            else:
                new_sub, new_cuts, improved, new_fracs, used = \
                    _lp_attempt_population(
                        hga, sub, jnp.asarray(cuts[idx], jnp.float32),
                        jnp.asarray(fracs[idx]), jnp.int32(remaining), k,
                        cap, edge_weight_override=edge_weight_override,
                        edge_weights_pop=sub_ew, incumbent=inc,
                        mig_budget=mb)
                improved = np.asarray(improved)
                if len(idx) < alpha:
                    parts = parts.at[jnp.asarray(idx)].set(new_sub)
                else:
                    parts = new_sub
            # unimproved lanes pass their cuts through the f32 carry
            # unchanged (all cuts originate f32), so this is pure update
            cuts[idx] = np.asarray(new_cuts, np.float64)
            fracs[idx] = np.asarray(new_fracs)
            improved_round[idx[improved]] = True
            remaining -= int(used)
            idx = idx[~improved]
        stall[active] = np.where(improved_round[active], 0,
                                 stall[active] + 1)
        done |= stall >= patience
    return np.asarray(parts), cuts


# --------------------------------------------------------------------------
# sequential FM (scan) for coarse levels
# --------------------------------------------------------------------------
#: Share of one device's memory that the dense FM route's incidences (one
#: [n_pad, m_pad] bf16 matrix per instance of a dispatch) may take; a
#: dispatch that would need more keeps the segment-sum route.
FM_DENSE_MEM_SHARE = 1 / 8
#: Device memory assumed where the backend reports no limit (one TPU v5e).
DEVICE_BYTES_FALLBACK = 16 * 2**30


@lru_cache(maxsize=1)
def _device_bytes() -> int:
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", DEVICE_BYTES_FALLBACK))


def fm_dense_budget() -> int:
    """Bytes the dense FM route's incidences may take on one device."""
    return int(_device_bytes() * FM_DENSE_MEM_SHARE)


def fm_gain_route(n_pad: int, m_pad: int, lanes: int = 1,
                  pin_axis: str | None = None,
                  backend: str | None = None) -> str:
    """How an FM pass dispatch assembles its per-move gains: "dense" (the
    level's dense incidence contracted on the matrix unit, no scatter in
    the move loop) or "segsum" (per-pin gathers and segment-sums).

    Decided from what the dispatch can observe, before it is traced:
    "segsum" on the CPU (a dense contraction per move step costs more
    there than the tiny coarse-level scatters), with pin tables sharded
    over ``pin_axis`` (that path exists for structures too large to
    replicate, where a dense incidence is the wrong tool), and when the
    ``lanes`` incidences of [n_pad, m_pad] bf16 the dispatch would hold
    exceed ``fm_dense_budget()``; "dense" otherwise.  ``backend``
    defaults to JAX's.  Both routes return the same partitions and
    cuts."""
    if pin_axis is not None:
        return "segsum"
    if (backend or jax.default_backend()) == "cpu":
        return "segsum"
    if lanes * n_pad * m_pad * 2 > fm_dense_budget():
        return "segsum"
    return "dense"


def _fm_pass_impl(hga: HypergraphArrays, part: jnp.ndarray, k: int,
                  cap: jnp.ndarray, steps: int,
                  k_live: jnp.ndarray | None = None,
                  incumbent: jnp.ndarray | None = None,
                  mig_budget: jnp.ndarray | None = None,
                  pin_axis: str | None = None,
                  assemble: str = "segsum",
                  mult: jnp.ndarray | None = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One FM pass: up to ``steps`` single moves (negative gains allowed),
    returns the best prefix (partition + its cut).

    The move loop is a ``while_loop`` that exits as soon as no feasible
    move exists (every vertex locked or infeasible) — once ``do`` turns
    False the state is frozen, so cutting the remaining iterations is
    exactly equivalent to the fixed-length scan it replaces, at a
    fraction of the cost.  Under ``vmap`` (the population path) the loop
    runs until ALL members are done; finished members' lanes are inert.

    ``k_live`` (optional traced scalar, instance axis, DESIGN.md §12):
    move targets ``j >= k_live`` are masked to NEG.  The flat argmax
    over [n_pad, k] preserves the row-major (v, j) order of the
    [n_pad, k_live] matrix a solo run would scan, so the selected move
    sequence — and therefore the best prefix — is bit-identical.

    ``incumbent`` [n_pad] + ``mig_budget`` (optional, DESIGN.md §14):
    the moved-vertex weight relative to the incumbent is carried through
    the loop state; a move whose migration delta would push it past the
    budget is masked to NEG exactly like a balance violation.  Every
    trajectory prefix then satisfies the budget by induction, so the
    best-prefix rollback is always feasible.

    ``pin_axis`` (DESIGN.md §15): pin tables row-sharded over that mesh
    axis — phi, the gain matrix and the per-move pin-count ``d`` arrive
    as psum'd int32/integer-f32 partials; every carried state leaf is
    [n_pad]/[m_pad]-indexed and identical on all shards, so the move
    sequence is bit-identical to the replicated pass.

    ``assemble`` (static, ``fm_gain_route``) picks how each move's gains
    and pin counts are assembled: "segsum" gathers and segment-sums over
    every pin per move; "dense" contracts the level's dense incidence
    ``mult`` (``metrics.pin_multiplicity``, built here when not given)
    with the edge terms and reads the moved vertex's pin counts as one
    row of it, so the move loop holds no scatter.  Both give the same
    integers (``metrics._gain_dense``), so the move sequence is
    bit-identical.
    """
    if assemble == "dense" and mult is None:
        mult = metrics.pin_multiplicity(hga)
    n_pad = hga.n_pad
    rows = jnp.arange(n_pad, dtype=jnp.int32)
    valid = (rows < hga.n) & (hga.vertex_weights > 0)
    phi0 = metrics.pins_in_block(hga, part, k, pin_axis=pin_axis)
    bw0 = metrics.block_weights(hga, part, k)
    cut0 = metrics.cutsize(hga, part, k, pin_axis=pin_axis)
    if incumbent is None:
        mig0 = jnp.float32(0.0)
    else:
        mig0 = jnp.where(part != incumbent, hga.vertex_weights, 0.0).sum()

    def body(carry):
        (part, phi, bw, locked, cur_cut, best_cut, best_part, mig_w,
         t, _) = carry
        # the gains come from plain XLA (this body is vmapped by the
        # population pass, so never a pallas_call); the own-block entries
        # are masked in ``score`` below
        if assemble == "dense":
            gains = metrics._gain_dense(hga, mult, phi, k)     # [n_pad, k]
        else:
            gains = metrics.gain_matrix(hga, part, k, phi=phi,
                                        assemble="segsum",
                                        pin_axis=pin_axis)    # [n_pad, k]
        own = jax.nn.one_hot(part, k, dtype=bool)
        feasible = (bw[None, :] + hga.vertex_weights[:, None]) <= cap + 1e-6
        score = jnp.where(own | ~feasible, NEG, gains)
        if k_live is not None:
            score = jnp.where(jnp.arange(k)[None, :] >= k_live, NEG, score)
        delta_mig = None
        if incumbent is not None:
            moved_tgt = (jnp.arange(k, dtype=jnp.int32)[None, :]
                         != incumbent[:, None]).astype(jnp.float32)
            moved_cur = (part != incumbent).astype(jnp.float32)
            delta_mig = hga.vertex_weights[:, None] * (
                moved_tgt - moved_cur[:, None])                # [n_pad, k]
            score = jnp.where(mig_w + delta_mig > mig_budget + 1e-6,
                              NEG, score)
        score = jnp.where((locked | ~valid)[:, None], NEG, score)
        flat = jnp.argmax(score)
        v = (flat // k).astype(jnp.int32)
        j = (flat % k).astype(jnp.int32)
        g = score.reshape(-1)[flat]
        do = g > NEG / 2  # any feasible move at all?

        b = part[v]
        if assemble == "dense":
            d = mult[v].astype(jnp.int32)                      # [m_pad]
        else:
            d = jax.ops.segment_sum(
                (hga.pin_vertex == v).astype(jnp.int32), hga.pin_edge,
                num_segments=hga.m_pad)                        # [m_pad]
            if pin_axis is not None:
                d = jax.lax.psum(d, pin_axis)  # v's pins span shards
        delta = (jax.nn.one_hot(j, k, dtype=phi.dtype)
                 - jax.nn.one_hot(b, k, dtype=phi.dtype))      # [k]
        phi_new = phi + d[:, None] * delta[None, :]
        bw_new = bw + hga.vertex_weights[v] * delta
        moved = do & (rows == v)                               # [n_pad]
        cut_new = cur_cut - g

        part = jnp.where(moved, j, part)
        phi = jnp.where(do, phi_new, phi)
        bw = jnp.where(do, bw_new, bw)
        locked = locked | moved
        cur_cut = jnp.where(do, cut_new, cur_cut)
        if incumbent is not None:
            mig_w = jnp.where(do, mig_w + delta_mig[v, j], mig_w)
        better = do & (cur_cut < best_cut - 1e-9)
        best_cut = jnp.where(better, cur_cut, best_cut)
        best_part = jnp.where(better, part, best_part)
        return (part, phi, bw, locked, cur_cut, best_cut, best_part,
                mig_w, t + 1, do)

    def cond(carry):
        t, alive = carry[-2], carry[-1]
        return (t < steps) & alive

    locked0 = jnp.zeros(n_pad, bool)
    init = (part, phi0, bw0, locked0, cut0, cut0, part, mig0,
            jnp.int32(0), jnp.bool_(True))
    out = jax.lax.while_loop(cond, body, init)
    return out[6], out[5]


_fm_pass = jax.jit(_fm_pass_impl,
                   static_argnames=("k", "steps", "assemble"))


def _fm_pass_population_impl(hga: HypergraphArrays, parts: jnp.ndarray,
                             k: int, cap: jnp.ndarray, steps: int,
                             edge_weights_pop: jnp.ndarray | None = None,
                             k_live: jnp.ndarray | None = None,
                             incumbent: jnp.ndarray | None = None,
                             mig_budget: jnp.ndarray | None = None,
                             pin_axis: str | None = None,
                             assemble: str = "segsum"
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # the dense incidence is built once from the shared structure, outside
    # the members' vmap, so every member's contraction reads the one copy
    mult = (metrics.pin_multiplicity(hga) if assemble == "dense"
            else None)
    fm = partial(_fm_pass_impl, k=k, cap=cap, steps=steps, k_live=k_live,
                 incumbent=incumbent, mig_budget=mig_budget,
                 pin_axis=pin_axis, assemble=assemble, mult=mult)
    if edge_weights_pop is None:
        return jax.vmap(lambda p: fm(hga, p))(parts)
    return jax.vmap(
        lambda p, ew: fm(metrics.member_arrays(hga, ew), p))(
            parts, edge_weights_pop)


#: One FM pass for all members: a single [alpha]-batched move scan
#: instead of alpha sequential scans.  With ``edge_weights_pop`` each
#: member's lane runs on its own edge-weight row (shared structure).
_fm_pass_population = partial(
    jax.jit, static_argnames=("k", "steps", "assemble"))(
        _fm_pass_population_impl)


@lru_cache(maxsize=32)
def _fm_pass_population_mesh(mesh, k: int, steps: int,
                             model: bool = False, assemble: str = "segsum"):
    """The batched FM pass shard_map'd over the ("pop", "model") mesh
    (DESIGN.md §11): structure replicated, member rows sharded over
    "pop".  FM lanes are fully row-independent (no collective needed);
    each shard's move loop even exits as soon as ITS lanes are done.

    With ``model`` (DESIGN.md §15) the pin tables are additionally
    row-sharded over "model" and the per-move pin reductions psum'd; the
    move selection runs on replicated values, so every model shard of a
    pop row takes the identical trip count and move sequence."""
    def body(hga, parts, cap, ew_pop, incumbent, mig_budget):
        return _fm_pass_population_impl(
            hga, parts, k, cap, steps, edge_weights_pop=ew_pop,
            incumbent=incumbent, mig_budget=mig_budget,
            pin_axis="model" if model else None, assemble=assemble)

    fn = shard_map(body, mesh,
                   in_specs=(_hga_specs(model), P("pop"), P(), P("pop"),
                             P(), P()),
                   out_specs=(P("pop"), P("pop")))
    return jax.jit(fn)


def fm_refine(hga: HypergraphArrays, part: np.ndarray, k: int, eps: float,
              max_passes: int = 8, step_budget: int | None = None
              ) -> Tuple[np.ndarray, float]:
    """Repeated FM passes until no pass improves the cut."""
    cap = metrics.balance_cap(hga.total_weight, k, eps)
    part = pad_part(part, hga.n_pad)
    cut = float(metrics.cutsize_jit(hga, part, k))
    # shape-derived so all pow2-bucketed levels share one compilation
    steps = step_budget or int(min(hga.n_pad, 1024))
    assemble = fm_gain_route(hga.n_pad, hga.m_pad)
    for _ in range(max_passes):
        cand, c = _fm_pass(hga, part, k, cap, steps, assemble=assemble)
        c = float(c)
        if c < cut - 1e-6:
            part, cut = cand, c
        else:
            break
    return np.asarray(part), cut


def _population_shard_devices():
    """Local devices for the ``chunk`` population path.  Returns None on
    a single-device host (tests pin one device; TPU/GPU pods and CPU
    hosts with ``--xla_force_host_platform_device_count`` expose
    several).  Draws from the survivor pool (``popshard.local_devices``)
    so a device loss re-routes the chunked tier too."""
    devs = popshard.local_devices()
    return devs if len(devs) > 1 else None


# Placements are memoised in popshard's mesh-driven placement cache (the
# per-device chunk path and the mesh path share it); kept under the old
# name for the regression tests.
_device_put_cached = popshard.device_put_cached

# Balance caps, keyed on (popshard.placement_token(hga), k, eps): the
# cap is a pure function of the level's total weight, so computing it
# once per level gives the placement cache a STABLE object to key on —
# `fm_refine_population` used to re-ship `cap` to every device on every
# call while carefully caching the (much larger) hypergraph placements
# right next to it.  The token (not a raw id()) makes the key immune to
# CPython id reuse after a level is garbage-collected.
_CAP_CACHE: dict = {}


def _cap_for(hga: HypergraphArrays, k: int, eps: float, target=None):
    """The balance cap for (hga, k, eps), optionally placed on a device
    or sharding — both the scalar and the placements are cached."""
    key = (popshard.placement_token(hga), int(k), float(eps))
    cap = _CAP_CACHE.get(key)
    if cap is None:
        cap = metrics.balance_cap(hga.total_weight, k, eps)
        _CAP_CACHE[key] = cap
        weakref.finalize(hga, _CAP_CACHE.pop, key, None)
    if target is None:
        return cap
    return popshard.device_put_cached(cap, target)


def _mesh_dispatch(hga: HypergraphArrays, k: int, eps: float,
                   model_shard: str | None = None):
    """Shared setup of a mesh-path dispatch (both tiers): the local
    ("pop", "model") mesh, its pop-axis size and row sharding, the
    structure placement + cap (shipped once per (level, mesh) through
    the placement cache), and whether this dispatch row-shards the pin
    tables over "model" (``model_shard``/``REPRO_MODEL_SHARD``,
    DESIGN.md §15) — in which case the structure ships in the
    model-sharded layout instead of replicated."""
    mesh = popshard.pop_mesh()
    rep = popshard.replicated(mesh)
    model = (popshard.resolve_model(model_shard) == "mesh"
             and popshard.model_axis_active(hga.p_pad, mesh))
    popshard.enforce_structure_budget(
        hga, mesh.shape["model"] if model else 1)
    hga_m = (popshard.model_put_cached(hga, mesh) if model
             else popshard.device_put_cached(hga, rep))
    return (mesh, mesh.shape["pop"], popshard.pop_sharding(mesh),
            hga_m, _cap_for(hga, k, eps, rep), model)


def _put_rows(arr, npop: int, pop_sh):
    """Pad a member-row batch to the pop-axis size and shard it."""
    return jax.device_put(jnp.asarray(popshard.pad_rows(arr, npop)),
                          pop_sh)


def fm_refine_population(hga: HypergraphArrays, parts, k: int, eps: float,
                         max_passes: int = 8,
                         step_budget: int | None = None,
                         edge_weights_pop=None, shard: str | None = None,
                         incumbent=None, mig_budget: float | None = None,
                         model_shard: str | None = None
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched ``fm_refine`` with per-member pass acceptance: a member
    stops improving exactly when the scalar loop would have broken.

    Multi-device routing (``shard``, None = ``REPRO_POP_SHARD``):
    ``mesh`` runs each pass shard_map'd over the ("pop", "model") mesh —
    structure replicated once per (level, mesh) through the placement
    cache, member rows sharded over "pop" (DESIGN.md §11); ``chunk`` is
    the legacy reference that slices the batch over
    ``jax.local_devices()`` with async dispatch; ``off`` stays on one
    device.  None of them changes results: members are row-independent,
    so all paths return bit-identical per-member partitions and cuts.

    ``incumbent`` [n] + ``mig_budget``: bounded migration (DESIGN.md
    §14), enforced move-by-move inside every member's pass.

    The gain route (``fm_gain_route``) is resolved once per call;
    ``repro.obs`` counts the lane-passes, the accepted ones and those
    on the dense route.
    """
    cap = _cap_for(hga, k, eps)
    parts = np.array(pad_parts(parts, hga.n_pad))  # writable host copy
    alpha = parts.shape[0]
    inc = mb = None
    if incumbent is not None:
        inc = pad_part(incumbent, hga.n_pad)
        mb = float(np.inf if mig_budget is None else mig_budget)
    if edge_weights_pop is not None:
        edge_weights_pop = np.asarray(edge_weights_pop, np.float32)
        cuts = np.asarray(metrics.cutsize_population_weighted(
            hga, jnp.asarray(parts), jnp.asarray(edge_weights_pop), k),
            np.float64)
    else:
        cuts = np.asarray(metrics.cutsize_population(hga, parts, k),
                          np.float64)
    steps = step_budget or int(min(hga.n_pad, 1024))
    done = np.zeros(alpha, bool)
    path = popshard.resolve(shard) if alpha > 1 else "off"
    devs = _population_shard_devices() if path == "chunk" else None
    if devs:
        hga_d = [_device_put_cached(hga, d) for d in devs]
        cap_d = [_cap_for(hga, k, eps, d) for d in devs]
        inc_d = ([jax.device_put(inc, d) for d in devs]
                 if inc is not None else [None] * len(devs))
    mesh_fn = None
    model = False
    if path == "mesh":
        mesh, npop, pop_sh, hga_m, cap_m, model = _mesh_dispatch(
            hga, k, eps, model_shard)
        if inc is not None:
            inc = jax.device_put(inc, popshard.replicated(mesh))
    else:
        popshard.enforce_structure_budget(hga, 1)
    # members share one incidence per device (structure replicated)
    assemble = fm_gain_route(hga.n_pad, hga.m_pad,
                             pin_axis="model" if model else None)
    if path == "mesh":
        mesh_fn = _fm_pass_population_mesh(mesh, k, steps, model, assemble)
    for _ in range(max_passes):
        idx = np.nonzero(~done)[0]  # compact: finished members drop out
        if len(idx) == 0:
            break
        obs.count("fm.lane_passes", len(idx))
        if assemble == "dense":
            obs.count("fm.dense_lanes", len(idx))
        sub = parts[idx]
        sub_ew = (edge_weights_pop[idx]
                  if edge_weights_pop is not None else None)
        if mesh_fn is not None:
            na = len(idx)
            out_p, out_c = mesh_fn(
                hga_m, _put_rows(sub, npop, pop_sh), cap_m,
                None if sub_ew is None
                else _put_rows(sub_ew, npop, pop_sh),
                inc, mb)
            cands = np.asarray(out_p)[:na]
            cs = np.asarray(out_c)[:na].astype(np.float64)
        elif devs and len(idx) > 1:
            ndev = min(len(devs), len(idx))
            bounds = [len(idx) * d // ndev for d in range(ndev + 1)]
            outs = []
            for di in range(ndev):  # async dispatch -> concurrent chunks
                chunk = jax.device_put(
                    jnp.asarray(sub[bounds[di]:bounds[di + 1]]), devs[di])
                ew_chunk = None
                if sub_ew is not None:
                    ew_chunk = jax.device_put(
                        jnp.asarray(sub_ew[bounds[di]:bounds[di + 1]]),
                        devs[di])
                outs.append(_fm_pass_population(
                    hga_d[di], chunk, k, cap_d[di], steps,
                    edge_weights_pop=ew_chunk, incumbent=inc_d[di],
                    mig_budget=mb, assemble=assemble))
            cands = np.concatenate([np.asarray(o[0]) for o in outs])
            cs = np.concatenate(
                [np.asarray(o[1]) for o in outs]).astype(np.float64)
        else:
            cands, cs = _fm_pass_population(
                hga, jnp.asarray(sub), k, cap, steps,
                edge_weights_pop=None if sub_ew is None
                else jnp.asarray(sub_ew), incumbent=inc,
                mig_budget=mb, assemble=assemble)
            cands = np.asarray(cands)
            cs = np.asarray(cs, np.float64)
        take = cs < cuts[idx] - 1e-6
        obs.count("fm.lane_accepted", take)
        if take.any():
            tidx = idx[take]
            parts[tidx] = cands[take]
            cuts[tidx] = cs[take]
        done[idx[~take]] = True
    return parts, cuts


# --------------------------------------------------------------------------
# combined per-level refinement + balance safety net
# --------------------------------------------------------------------------
def refine(hga: HypergraphArrays, part: np.ndarray, k: int, eps: float,
           fm_node_limit: int = 4096, **kw) -> Tuple[np.ndarray, float]:
    part, cut = lp_refine(hga, part, k, eps, **kw)
    if int(hga.n) <= fm_node_limit:
        part, cut = fm_refine(hga, part, k, eps)
    return part, cut


def refine_population(hga: HypergraphArrays, parts, k: int, eps: float,
                      fm_node_limit: int = 4096, edge_weights_pop=None,
                      shard: str | None = None, incumbent=None,
                      mig_budget: float | None = None,
                      model_shard: str | None = None, **kw
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Two-tier refinement for the whole population in batched dispatches
    (the production path of ``impart_partition``, ``vcycle`` and the
    mutation cohort's population V-cycle).  Both tiers route through the
    ``REPRO_POP_SHARD`` dispatcher (``shard`` overrides, DESIGN.md §11)
    and the ``REPRO_MODEL_SHARD`` structure dispatcher (``model_shard``
    overrides, DESIGN.md §15).  ``incumbent`` + ``mig_budget`` bound
    migration through BOTH tiers (DESIGN.md §14).  Returns
    (parts [alpha, n_pad], cuts [alpha])."""
    parts, cuts = lp_refine_population(hga, parts, k, eps,
                                       edge_weights_pop=edge_weights_pop,
                                       shard=shard, incumbent=incumbent,
                                       mig_budget=mig_budget,
                                       model_shard=model_shard, **kw)
    if int(hga.n) <= fm_node_limit:
        parts, cuts = fm_refine_population(
            hga, parts, k, eps, edge_weights_pop=edge_weights_pop,
            shard=shard, incumbent=incumbent, mig_budget=mig_budget,
            model_shard=model_shard)
    return parts, cuts


def rebalance(hg_vertex_weights: np.ndarray, part: np.ndarray, k: int,
              eps: float, rng: np.random.Generator | None = None
              ) -> np.ndarray:
    """Host safety net: spill the lightest vertices out of overfull blocks
    and re-place them (heaviest first) into blocks that actually have
    headroom, iterating to a fixpoint.

    Moving into ``argmin(bw)`` unconditionally is NOT safe: a target that
    was already processed can end above the cap.  Placement therefore only
    targets blocks where the vertex fits under the cap; only when a vertex
    fits nowhere (infeasible instance, e.g. one vertex heavier than the
    cap) does it fall back to the least-loaded block.
    """
    del rng  # kept for signature compatibility; the procedure is greedy
    part = np.asarray(part).copy()
    w = np.asarray(hg_vertex_weights, np.float64)
    n = len(part)
    total = w.sum()
    cap = (1.0 + eps) * np.ceil(total / k)
    bw = np.zeros(k)
    np.add.at(bw, part[:n], w)

    for _ in range(k + 1):  # forced placements may need another pass
        spill: list = []
        for b in range(k):
            if bw[b] <= cap + 1e-6:
                continue
            members = np.nonzero(part == b)[0]
            order = members[np.argsort(w[members], kind="stable")]
            for v in order:  # evict lightest first
                if bw[b] <= cap + 1e-6:
                    break
                spill.append(v)
                bw[b] -= w[v]
        if not spill:
            break
        # place heaviest first (best-fit decreasing)
        spill.sort(key=lambda v: -w[v])
        for v in spill:
            fits = np.nonzero(bw + w[v] <= cap + 1e-6)[0]
            tgt = (fits[np.argmin(bw[fits])] if len(fits)
                   else int(np.argmin(bw)))
            part[v] = tgt
            bw[tgt] += w[v]
    return part
