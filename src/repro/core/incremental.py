"""Incremental repartitioning: warm-start V-cycles with bounded
migration for drifting workloads (DESIGN.md §14).

A refresh takes (previous assignment, reweighted/edited hypergraph,
migration budget) and produces a new assignment without rebuilding the
world:

* **Hierarchy reuse** — ``IncrementalState`` caches the multilevel
  hierarchy keyed on a structure token (crc32 over pins/edge_offsets).
  When only weights drift, every level's contraction is *replayed* with
  the stored cluster maps: the host path re-runs ``contract`` per level
  and attaches the new weights through ``with_edge_weights`` (donated
  structure arrays — only the weight leaves re-ship to the device); the
  device path re-runs ``contract_arrays`` and swaps the weight leaves
  into the resident ``HierarchyArrays`` with ``dataclasses.replace``.
  Identical weights reuse the resident hierarchy as-is; pin edits change
  the structure token and fall back to the structure-patching path — a
  rebuild restricted by the incumbent (``restrict_part``), so the
  incumbent still projects cut-exactly through the new hierarchy.

* **Incumbent projection** — the cached hierarchy may have been built
  around an *older* assignment, so the current incumbent is projected by
  weighted majority per cluster.  The per-level budget is reduced by the
  residual (the weight of vertices disagreeing with their cluster's
  majority block): for any coarse candidate ``p``, true fine migration
  ≤ coarse migration + residual, so enforcing
  ``coarse migration ≤ budget − residual`` keeps every accepted member
  feasible at the finest level.  At zero drift the projection is exact
  and the residual is zero, so the warm path is bit-identical to a
  fresh restricted build.

* **Bounded migration** — the per-level (incumbent, budget) pair feeds
  ``refine_population``'s second capacity-style objective (moved-vertex
  weight ≤ budget, traced through both LP and FM tiers).  Final
  selection keeps only members within budget at the finest level and
  falls back to the incumbent when nothing feasible beats it.

* **k-change** — elastic device loss remaps the incumbent
  ``b -> b % k_new`` and runs the same pipeline at the surviving device
  count; a cached hierarchy is reusable whenever ``k_new <= k_built``
  (the coarsest level is only ever *finer* than the new target).
"""

from __future__ import annotations

import dataclasses
import os
import time
import zlib
from typing import List, Optional, Tuple

import numpy as np
import jax.numpy as jnp

from .. import obs
from . import dcoarsen, metrics
from . import refine as refine_mod
from .coarsen import Hierarchy, Level
from .hypergraph import (HierarchyArrays, DeviceLevel, Hypergraph,
                         contract, contract_arrays)

__all__ = [
    "IncrementalConfig", "IncrementalResult", "IncrementalState",
    "incremental_partition", "repartition_k_change", "structure_token",
    "project_incumbent", "seed_incumbent_population", "select_best",
    "incr_reuse_enabled", "incr_perturb_frac",
]


# --------------------------------------------------------------------------
# REPRO_INCR_* knobs (docs/reference.md).  Bad values go through
# ``warn_env_once`` — never a silent fallback.

def incr_reuse_enabled() -> bool:
    """``REPRO_INCR_REUSE`` — hierarchy reuse across refreshes
    ("on"/"off", default on).  Off rebuilds the hierarchy every solve
    (the from-scratch arm of the zero-drift parity test)."""
    raw = os.environ.get("REPRO_INCR_REUSE", "on").strip().lower()
    if raw not in ("on", "off"):
        from repro.serve.faults import warn_env_once
        warn_env_once("REPRO_INCR_REUSE", raw, "on")
        return True
    return raw == "on"


def incr_perturb_frac() -> float:
    """``REPRO_INCR_PERTURB`` — fraction of the migration budget each
    perturbed clone spends on seed moves away from the incumbent
    (float in [0, 1], default 0.5)."""
    raw = os.environ.get("REPRO_INCR_PERTURB", "").strip()
    if not raw:
        return 0.5
    try:
        v = float(raw)
        if not 0.0 <= v <= 1.0:
            raise ValueError
        return v
    except ValueError:
        from repro.serve.faults import warn_env_once
        warn_env_once("REPRO_INCR_PERTURB", raw, "0.5")
        return 0.5


# --------------------------------------------------------------------------
# Config / result

@dataclasses.dataclass
class IncrementalConfig:
    k: int
    eps: float = 0.08
    alpha: int = 4               # population size (incumbent + clones)
    # Migration budget as a fraction of total vertex weight; None =
    # unbounded (plain warm start).  For k-change solves the forced
    # remap does not count — the budget bounds movement beyond it.
    migration_frac: Optional[float] = 0.1
    seed: int = 0
    lp_iters: int = 8
    fm_node_limit: int = 4096
    contraction_limit_factor: int = 64
    perturb_frac: Optional[float] = None   # None -> REPRO_INCR_PERTURB
    reuse: Optional[bool] = None           # None -> REPRO_INCR_REUSE
    pop_shard: Optional[str] = None        # None -> REPRO_POP_SHARD
    model_shard: Optional[str] = None      # None -> REPRO_MODEL_SHARD

    def __post_init__(self):
        if self.k < 2:
            raise ValueError(f"k must be >= 2, got {self.k}")
        if self.migration_frac is not None and self.migration_frac < 0:
            raise ValueError("migration_frac must be >= 0 or None")


@dataclasses.dataclass
class IncrementalResult:
    part: np.ndarray             # [n] int32
    cut: float
    migration_weight: float      # moved-vertex weight vs the incumbent
    budget_weight: float         # absolute budget (inf when unbounded)
    reused: str                  # "cold" | "resident" | "replayed" | "patched"
    wall_s: float
    levels: int
    cuts: np.ndarray             # per-member finest-level cuts


# --------------------------------------------------------------------------
# Structure token + hierarchy cache

def structure_token(hg: Hypergraph) -> Tuple[int, int, int, int]:
    """crc32 over the structure arrays — weights excluded by design, so
    weight drift keeps the token and pin edits change it."""
    t = zlib.crc32(np.ascontiguousarray(hg.pins, np.int32).tobytes())
    t = zlib.crc32(np.ascontiguousarray(hg.edge_offsets, np.int64)
                   .tobytes(), t)
    return (t, int(hg.n), int(hg.m), int(hg.num_pins))


def _replay_host(hier: Hierarchy, hg_new: Hypergraph) -> Hierarchy:
    """Re-run every stored contraction on the drifted weights.  The
    cluster maps are structure-only, so ``contract`` reproduces each
    level's pins exactly; the old level's Hypergraph donates its device
    arrays through ``with_edge_weights`` and only weight leaves re-ship."""
    old0 = hier.levels[0].hg
    hg0 = old0.with_edge_weights(hg_new.edge_weights,
                                 hg_new.vertex_weights)
    levels = [Level(hg0, hier.levels[0].cluster_id, hier.levels[0].part)]
    for li in range(1, len(hier.levels)):
        old = hier.levels[li]
        coarse, _ = contract(levels[li - 1].hg, old.cluster_id, old.hg.n)
        hg_li = old.hg.with_edge_weights(coarse.edge_weights,
                                         coarse.vertex_weights)
        levels.append(Level(hg_li, old.cluster_id, old.part))
    return Hierarchy(levels=levels)


def _replay_device(hier: HierarchyArrays,
                   hg_new: Hypergraph) -> HierarchyArrays:
    """Device-path replay: swap the finest level's weight leaves, then
    re-run ``contract_arrays`` per stored cluster map.  Its output keeps
    the finer level's padding; slicing to the old level's bucket is
    exactly the rebucket the original build performed, so at zero drift
    the replayed leaves are bit-identical to a fresh build."""
    lv0 = hier.levels[0]
    ew = np.zeros(lv0.hga.m_pad, np.float32)
    ew[:lv0.m] = hg_new.edge_weights
    vw = np.zeros(lv0.hga.n_pad, np.float32)
    vw[:lv0.n] = hg_new.vertex_weights
    hga0 = dataclasses.replace(lv0.hga, edge_weights=jnp.asarray(ew),
                               vertex_weights=jnp.asarray(vw))
    levels = [DeviceLevel(hga0, lv0.cluster_id, lv0.n, lv0.m, lv0.p,
                          part=lv0.part, host_hg=lv0.host_hg)]
    for li in range(1, len(hier.levels)):
        old = hier.levels[li]
        coarse, _ = contract_arrays(levels[li - 1].hga, old.cluster_id,
                                    old.n)
        hga_li = dataclasses.replace(
            old.hga,
            edge_weights=coarse.edge_weights[:old.hga.m_pad],
            vertex_weights=coarse.vertex_weights[:old.hga.n_pad])
        levels.append(DeviceLevel(hga_li, old.cluster_id, old.n, old.m,
                                  old.p, part=old.part, host_hg=None))
    return HierarchyArrays(levels=levels)


def _replay_weights(hier, hg_new: Hypergraph):
    if isinstance(hier, HierarchyArrays):
        return _replay_device(hier, hg_new)
    return _replay_host(hier, hg_new)


class IncrementalState:
    """Cross-refresh resident state: one cached hierarchy keyed on
    (structure token, seed, contraction limit).  ``hierarchy_for``
    classifies the refresh — identical weights reuse the resident
    hierarchy untouched, weight drift replays the contractions, a
    structure change (pin edits) rebuilds restricted by the incumbent
    (the structure-patching fallback), and a k larger than the cached
    build's rebuilds because the coarsest level may be too coarse."""

    def __init__(self):
        self._entry: Optional[dict] = None

    def hierarchy_for(self, hg: Hypergraph, incumbent: np.ndarray,
                      cfg: IncrementalConfig):
        token = structure_token(hg)
        e = self._entry
        if (e is not None and e["token"] == token
                and e["seed"] == cfg.seed
                and e["clf"] == cfg.contraction_limit_factor
                and cfg.k <= e["k_built"]):
            old_hg = e["hg"]
            if (np.array_equal(old_hg.edge_weights, hg.edge_weights)
                    and np.array_equal(old_hg.vertex_weights,
                                       hg.vertex_weights)):
                return e["hier"], "resident"
            hier = _replay_weights(e["hier"], hg)
            e["hier"], e["hg"] = hier, hg
            return hier, "replayed"
        how = "cold" if e is None else "patched"
        with obs.span("coarsen"):
            hier = dcoarsen.build_hierarchy(
                hg, cfg.k, seed=cfg.seed, restrict_part=incumbent,
                contraction_limit_factor=cfg.contraction_limit_factor,
                model_shard=cfg.model_shard)
        self._entry = dict(token=token, k_built=cfg.k, seed=cfg.seed,
                           clf=cfg.contraction_limit_factor, hier=hier,
                           hg=hg)
        return hier, how


# --------------------------------------------------------------------------
# Incumbent projection with residual-adjusted budgets

def project_incumbent(hier, incumbent: np.ndarray, k: int,
                      budget_w: float
                      ) -> Tuple[List[np.ndarray], List[float]]:
    """Per-level majority-projected incumbents + conservative budgets.

    Level ``li``'s incumbent assigns each cluster its members' weighted
    majority block; the residual (weight of disagreeing members) is
    subtracted from the budget.  Because true fine migration of any
    level-``li`` candidate is bounded by its coarse migration plus the
    residual, enforcing the reduced budget at every level keeps all
    accepted members within the true budget.  When the hierarchy was
    built with ``restrict_part=incumbent`` every cluster is pure, the
    majority IS the exact projection and the residual is zero.
    """
    inc0 = np.asarray(incumbent, np.int32)
    n0 = hier.level_n(0)
    vw0 = np.asarray(hier.level_arrays(0).vertex_weights,
                     np.float64)[:n0]
    total = float(vw0.sum())
    incs: List[np.ndarray] = [inc0]
    buds: List[float] = [float(budget_w)]
    cur_map = np.arange(n0)
    for li in range(1, hier.num_levels):
        cid = np.asarray(hier.levels[li].cluster_id)
        cur_map = cid[cur_map]
        n_li = hier.level_n(li)
        w = np.zeros((n_li, k), np.float64)
        np.add.at(w, (cur_map, inc0), vw0)
        incs.append(w.argmax(axis=1).astype(np.int32))
        residual = total - float(w.max(axis=1).sum())
        buds.append(float(budget_w) - residual)
    return incs, buds


# --------------------------------------------------------------------------
# Incumbent-seeded population

def seed_incumbent_population(hier, inc_L: np.ndarray, budget_L: float,
                              cfg: IncrementalConfig) -> np.ndarray:
    """UNREFINED coarsest-level seeds: member 0 is the projected
    incumbent exactly; clones perturb it with balance-safe,
    migration-safe random moves (each clone spends at most
    ``perturb_frac`` of the level budget).  The refinement ladder's
    first step refines this level, so the standalone solve and the
    service install produce identical trajectories by construction."""
    li = hier.num_levels - 1
    n_l = hier.level_n(li)
    hga = hier.level_arrays(li)
    vw = np.asarray(hga.vertex_weights, np.float64)[:n_l]
    cap = float(metrics.balance_cap(float(vw.sum()), cfg.k, cfg.eps))
    bw = np.zeros(cfg.k)
    np.add.at(bw, inc_L, vw)
    pfrac = (incr_perturb_frac() if cfg.perturb_frac is None
             else cfg.perturb_frac)
    per_budget = max(float(budget_L), 0.0) * pfrac
    members = [inc_L.astype(np.int32)]
    for i in range(1, cfg.alpha):
        rng = np.random.default_rng(
            zlib.crc32(f"incr:{cfg.seed}:{i}".encode()) & 0x7FFFFFFF)
        clone = inc_L.astype(np.int32).copy()
        bw_c = bw.copy()
        spent = 0.0
        for v in rng.permutation(n_l):
            if spent >= per_budget:
                break
            if vw[v] <= 0.0 or spent + vw[v] > per_budget:
                continue
            tgt = int(rng.integers(0, cfg.k))
            if tgt == clone[v] or bw_c[tgt] + vw[v] > cap + 1e-6:
                continue
            bw_c[clone[v]] -= vw[v]
            bw_c[tgt] += vw[v]
            clone[v] = tgt
            spent += vw[v]
        members.append(clone)
    return np.stack(members)


# --------------------------------------------------------------------------
# Budget-aware selection

def select_best(parts0: np.ndarray, cuts: np.ndarray,
                incumbent: np.ndarray, inc_cut: float, vw: np.ndarray,
                budget_w: float) -> Tuple[np.ndarray, float, float]:
    """Best finest-level member with migration <= budget; the incumbent
    (zero migration) competes as a fallback and wins strictly-better
    cut ties, so the result can never be worse than keeping the old
    assignment."""
    parts0 = np.asarray(parts0)
    cuts = np.asarray(cuts, np.float64)
    migs = ((parts0 != incumbent[None, :]) * vw[None, :]).sum(axis=1)
    ok = migs <= budget_w + 1e-6
    best = None
    for i in np.argsort(cuts, kind="stable"):
        if ok[i]:
            best = int(i)
            break
    if best is None or float(inc_cut) < cuts[best] - 1e-9:
        return np.asarray(incumbent, np.int32), float(inc_cut), 0.0
    return (parts0[best].astype(np.int32), float(cuts[best]),
            float(migs[best]))


# --------------------------------------------------------------------------
# The solve

def incremental_partition(hg: Hypergraph, incumbent,
                          cfg: IncrementalConfig,
                          state: Optional[IncrementalState] = None
                          ) -> IncrementalResult:
    """Warm-start repartition of ``hg`` around ``incumbent`` with moved
    weight bounded by ``cfg.migration_frac`` of the total.  Passing a
    ``state`` enables hierarchy reuse across refreshes (gated by
    ``cfg.reuse`` / ``REPRO_INCR_REUSE``)."""
    t0 = time.perf_counter()
    inc0 = np.asarray(incumbent, np.int32)
    if inc0.shape[0] != hg.n:
        raise ValueError(f"incumbent has {inc0.shape[0]} entries for "
                         f"{hg.n} vertices")
    if inc0.min(initial=0) < 0 or inc0.max(initial=0) >= cfg.k:
        raise ValueError("incumbent block ids out of range")
    total_w = float(np.sum(hg.vertex_weights))
    budget_w = (np.inf if cfg.migration_frac is None
                else float(cfg.migration_frac) * total_w)
    reuse = (incr_reuse_enabled() if cfg.reuse is None else cfg.reuse)
    if state is not None and reuse:
        hier, how = state.hierarchy_for(hg, inc0, cfg)
    else:
        with obs.span("coarsen"):
            hier = dcoarsen.build_hierarchy(
                hg, cfg.k, seed=cfg.seed, restrict_part=inc0,
                contraction_limit_factor=cfg.contraction_limit_factor,
                model_shard=cfg.model_shard)
        how = "cold"
    incs, buds = project_incumbent(hier, inc0, cfg.k, budget_w)
    top = hier.num_levels - 1
    parts = seed_incumbent_population(hier, incs[top], buds[top], cfg)
    cuts = None
    for li in range(top, -1, -1):
        if li < top:
            parts = hier.project_pop(parts, li + 1)
        parts, cuts = refine_mod.refine_population(
            hier.level_arrays(li), parts, cfg.k, cfg.eps,
            max_iters=cfg.lp_iters, fm_node_limit=cfg.fm_node_limit,
            shard=cfg.pop_shard, model_shard=cfg.model_shard,
            incumbent=incs[li], mig_budget=buds[li])
    hga0 = hier.level_arrays(0)
    inc_cut = float(metrics.cutsize(
        hga0, refine_mod.pad_part(inc0, hga0.n_pad), cfg.k))
    parts0 = np.asarray(parts)[:, :hg.n]
    vw = np.asarray(hg.vertex_weights, np.float64)
    part, cut, mig = select_best(parts0, np.asarray(cuts), inc0,
                                 inc_cut, vw, budget_w)
    return IncrementalResult(
        part=part, cut=cut, migration_weight=mig, budget_weight=budget_w,
        reused=how, wall_s=time.perf_counter() - t0,
        levels=hier.num_levels, cuts=np.asarray(cuts, np.float64))


def repartition_k_change(hg: Hypergraph, incumbent, k_new: int,
                         cfg: IncrementalConfig,
                         state: Optional[IncrementalState] = None
                         ) -> IncrementalResult:
    """Forced k-change (elastic device loss): remap incumbent blocks
    ``b -> b % k_new`` and run the incremental pipeline at ``k_new``.
    The migration budget bounds movement *beyond* the forced remap.  A
    cached hierarchy stays reusable because device loss only shrinks k
    (``k_new <= k_built`` keeps the coarsest level fine enough)."""
    inc = np.asarray(incumbent, np.int32) % k_new
    cfg2 = dataclasses.replace(cfg, k=k_new)
    return incremental_partition(hg, inc, cfg2, state=state)
