"""Incremental repartitioning subsystem (DESIGN.md §14).

Covers the acceptance contracts: migration-cap enforcement on every
accepted member, warm-start vs from-scratch bit-parity at zero drift,
incremental-request service parity across every ``REPRO_POP_SHARD``
path, the structure-patching fallback for pin edits, and the elastic
device-loss recovery wall-clock regression.
"""
import time

import numpy as np
import pytest

from repro import obs
from repro.core import (incremental_partition, repartition_k_change,
                        IncrementalConfig, IncrementalState, metrics,
                        popshard, refine)
from repro.core import incremental as incremental_mod
from repro.core.dcoarsen import build_hierarchy
from repro.data.hypergraphs import (_modular_netlist, drift_stream,
                                    random_hypergraph)
from repro.runtime.elastic import repartition_after_loss
from repro.serve.partition_service import (PartitionRequest,
                                           PartitionService)
from tests import parity

K, EPS = 8, 0.08


@pytest.fixture(scope="module")
def base_case():
    hg = _modular_netlist(500, 700, seed=11, n_modules=8, p_local=0.8,
                          fanout_tail=1.5)
    svc = PartitionService(slots=1, shard="off")
    svc.submit(PartitionRequest("seed", hg, K, eps=EPS))
    res = svc.drain()[0]
    return hg, np.asarray(res.part, np.int32)


# --------------------------------------------------------------------------
# migration cap: every accepted member of every refinement dispatch
# --------------------------------------------------------------------------
def test_migration_cap_enforced_per_member(base_case):
    """Members that start within budget stay within budget through both
    LP and FM tiers (the invariant the ladder relies on — seeds are
    constructed within budget, so every accepted member stays there)."""
    hg, inc = base_case
    hga = hg.arrays()
    rng = np.random.default_rng(3)
    vw0 = np.asarray(hg.vertex_weights, np.float64)
    budget = 0.05 * float(vw0.sum())
    parts = []
    for _ in range(4):
        p = inc.copy()
        spent = 0.0
        for v in rng.permutation(hg.n):  # bounded perturbation seeds
            if spent + vw0[v] > 0.5 * budget:
                break
            p[v] = rng.integers(0, K)
            spent += vw0[v] if p[v] != inc[v] else 0.0
        parts.append(p)
    out, cuts = refine.refine_population(hga, parts, K, EPS,
                                         incumbent=inc, mig_budget=budget)
    out = np.asarray(out)[:, :hg.n]
    vw = np.asarray(hg.vertex_weights, np.float64)
    for a in range(out.shape[0]):
        moved = float(vw[out[a] != inc].sum())
        assert moved <= budget + 1e-4, (a, moved, budget)
    # unbounded (None) stays bit-identical to the pre-§14 code path
    p0, c0 = refine.refine_population(hga, [p.copy() for p in parts],
                                      K, EPS)
    p1, c1 = refine.refine_population(hga, [p.copy() for p in parts],
                                      K, EPS, incumbent=inc,
                                      mig_budget=None)
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))


def test_incremental_result_respects_budget(base_case):
    hg, inc = base_case
    drifted = drift_stream(hg, 1, magnitude=0.3, tag="cap")[0]
    cfg = IncrementalConfig(k=K, eps=EPS, alpha=4, migration_frac=0.05,
                            seed=0)
    res = incremental_partition(drifted, inc, cfg)
    vw = np.asarray(hg.vertex_weights, np.float64)
    moved = float(vw[np.asarray(res.part) != inc].sum())
    assert moved <= res.budget_weight + 1e-4
    assert abs(moved - res.migration_weight) <= 1e-4
    # the answer is a valid balanced partition at least as good as the
    # incumbent on the drifted weights
    hga = drifted.arrays()
    inc_cut = float(metrics.cutsize(hga, refine.pad_part(inc, hga.n_pad),
                                    K))
    assert res.cut <= inc_cut + 1e-4


# --------------------------------------------------------------------------
# zero-drift warm vs from-scratch bit-parity (hierarchy replay is exact)
# --------------------------------------------------------------------------
def test_zero_drift_warm_parity(base_case):
    hg, inc = base_case
    cfg = IncrementalConfig(k=K, eps=EPS, alpha=4, migration_frac=0.1,
                            seed=0)
    st = IncrementalState()
    incremental_partition(hg, inc, cfg, state=st)  # populate the cache
    warm = incremental_partition(hg, inc, cfg, state=st)
    assert warm.reused == "resident"
    scratch = incremental_partition(hg, inc, cfg, state=None)
    assert scratch.reused == "cold"
    np.testing.assert_array_equal(warm.part, scratch.part)
    assert warm.cut == scratch.cut
    assert warm.migration_weight == scratch.migration_weight


def test_weight_replay_bit_exact_at_zero_drift(base_case):
    """The replay machinery itself: re-running every stored contraction
    on an identical-valued (but distinct) weight array reproduces every
    level's weight leaves bit-exactly and ships no structure."""
    hg, inc = base_case
    hier = build_hierarchy(hg, K, seed=0, restrict_part=inc)
    same = hg.with_edge_weights(hg.edge_weights.copy())
    rep = incremental_mod._replay_weights(hier, same)
    for li in range(hier.num_levels):
        a = hier.level_arrays(li)
        b = rep.level_arrays(li)
        np.testing.assert_array_equal(np.asarray(a.edge_weights),
                                      np.asarray(b.edge_weights))
        np.testing.assert_array_equal(np.asarray(a.vertex_weights),
                                      np.asarray(b.vertex_weights))


def test_structure_edit_falls_back_to_patch(base_case):
    hg, inc = base_case
    cfg = IncrementalConfig(k=K, eps=EPS, alpha=3, migration_frac=0.2,
                            seed=0)
    st = IncrementalState()
    r0 = incremental_partition(hg, inc, cfg, state=st)
    assert r0.reused == "cold"
    edited = drift_stream(hg, 1, magnitude=0.1, pin_edit_frac=0.05,
                          tag="edit")[0]
    assert incremental_mod.structure_token(edited) \
        != incremental_mod.structure_token(hg)
    r1 = incremental_partition(edited, np.asarray(r0.part), cfg, state=st)
    assert r1.reused == "patched"
    # and weight-only drift on the edited structure now replays
    redrift = drift_stream(edited, 1, magnitude=0.2, tag="edit2")[0]
    r2 = incremental_partition(redrift, np.asarray(r1.part), cfg,
                               state=st)
    assert r2.reused == "replayed"


# --------------------------------------------------------------------------
# drift_stream determinism
# --------------------------------------------------------------------------
def test_drift_stream_deterministic():
    hg = random_hypergraph(300, 450, seed=9)
    a = drift_stream(hg, 3, magnitude=0.25, vertex_magnitude=0.1,
                     tag="det")
    b = drift_stream(hg, 3, magnitude=0.25, vertex_magnitude=0.1,
                     tag="det")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.edge_weights, y.edge_weights)
        np.testing.assert_array_equal(x.vertex_weights, y.vertex_weights)
    # pure weight drift shares the base's structure arrays outright
    assert a[0].pins is hg.pins and a[2].pins is hg.pins


# --------------------------------------------------------------------------
# service parity across every (REPRO_POP_SHARD, REPRO_MODEL_SHARD) combo
# --------------------------------------------------------------------------
SERVICE_GRID = parity.grid(pop_shard=popshard.POP_SHARD_PATHS,
                           model_shard=(None, "mesh"))


@pytest.mark.parametrize("combo", parity.params(SERVICE_GRID))
def test_service_incremental_parity(base_case, combo):
    hg, inc = base_case
    drifted = drift_stream(hg, 1, magnitude=0.3, tag="svc")[0]
    other = _modular_netlist(420, 560, seed=21, n_modules=6, p_local=0.8,
                             fanout_tail=1.5)
    svc = PartitionService(slots=4, shard=combo.pop_shard or "off",
                           model_shard=combo.model_shard or "off")
    incr_req = PartitionRequest("incr", drifted, K, eps=EPS,
                                incumbent=inc, migration_frac=0.08)
    cold_req = PartitionRequest("cold", other, K, eps=EPS)
    svc.submit(incr_req)
    svc.submit(cold_req)  # co-batched cold traffic must not perturb it
    res = {r.name: r for r in svc.drain()}
    p_solo, c_solo = svc.solve_solo(
        PartitionRequest("incr", drifted, K, eps=EPS, incumbent=inc,
                         migration_frac=0.08))
    parity.assert_parity(
        (res["incr"].part, np.float64(res["incr"].cut)),
        (np.asarray(p_solo), np.float64(c_solo)),
        label=f"{combo.id} incr vs solo")
    p_cold, c_cold = svc.solve_solo(
        PartitionRequest("cold", other, K, eps=EPS))
    parity.assert_parity(
        (res["cold"].part, np.float64(res["cold"].cut)),
        (np.asarray(p_cold), np.float64(c_cold)),
        label=f"{combo.id} cold vs solo")
    vw = np.asarray(hg.vertex_weights, np.float64)
    moved = float(vw[res["incr"].part != inc].sum())
    assert moved <= 0.08 * float(vw.sum()) + 1e-4
    assert res["incr"].migration_weight is not None
    assert res["cold"].migration_weight is None


def test_service_rejects_invalid_incumbent(base_case):
    hg, inc = base_case
    svc = PartitionService(slots=1, shard="off")
    bad = PartitionRequest("bad", hg, K, incumbent=inc[:-3])
    res = svc.submit(bad)
    assert res is not None and res.status == "rejected"


# --------------------------------------------------------------------------
# elastic: warm k-change recovery beats from-scratch on wall clock
# --------------------------------------------------------------------------
def test_device_loss_recovery_wall_clock(base_case):
    hg, _ = base_case
    k_old, k_new = 8, 6
    cfg = IncrementalConfig(k=k_old, eps=EPS, alpha=4,
                            migration_frac=0.25, seed=0)
    st = IncrementalState()
    rng = np.random.default_rng(5)
    inc0 = refine.rebalance(hg.vertex_weights,
                            rng.integers(0, k_old, hg.n).astype(np.int32),
                            k_old, EPS)
    placed = incremental_partition(hg, inc0, cfg, state=st)

    def scratch():
        svc = PartitionService(slots=1, shard="off")
        svc.submit(PartitionRequest("s", hg, k_new, eps=EPS))
        return svc.drain()[0]

    def warm_recovery():
        return repartition_after_loss(hg, np.asarray(placed.part), k_new,
                                      eps=EPS, state=st)

    def timed(arm):
        """(best wall of three runs, the last run's result, its spans)."""
        walls = []
        for _ in range(3):
            obs.reset()
            obs.enable()
            t0 = time.perf_counter()
            res = arm()
            walls.append(time.perf_counter() - t0)
            obs.disable()
        return min(walls), res, obs.snapshot()["spans"]

    # one untimed round compiles both pipelines' engines
    scratch()
    repartition_after_loss(hg, np.asarray(placed.part), k_new, eps=EPS,
                           state=IncrementalState())

    try:
        t_cold, cold_res, cold_spans = timed(scratch)
        t_warm, warm, warm_spans = timed(warm_recovery)
    finally:
        obs.disable()
        obs.reset()

    # the claim behind the wall clock: recovery skips the coarsening
    # rebuild that a from-scratch solve makes
    assert "coarsen" not in warm_spans, warm_spans
    assert cold_spans["coarsen"]["count"] == 1
    # the survivors' resident hierarchy is reused outright (weights are
    # unchanged at loss time, k only shrinks) — no coarsening rebuild
    assert warm.reused == "resident"
    assert np.asarray(warm.part).max() < k_new
    vw = np.asarray(hg.vertex_weights, np.float64)
    forced = np.asarray(placed.part, np.int32) % k_new
    moved = float(vw[np.asarray(warm.part) != forced].sum())
    assert moved <= warm.budget_weight + 1e-4
    assert t_warm < t_cold, (t_warm, t_cold)
    assert cold_res.cut is not None  # scratch arm really solved
