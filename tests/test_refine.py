"""Refinement invariants: never unbalances, never worsens the cut; the
FM pass's two gain routes agree bit for bit."""
from functools import partial

import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_compat import given, settings, st

from repro import obs
from repro.core import instances, metrics, refine
from repro.core.hypergraph import Hypergraph


def _rand_hg(rng, n, m):
    edges = [rng.choice(n, size=int(rng.integers(2, min(6, n))),
                        replace=False) for _ in range(m)]
    return Hypergraph.from_edge_lists(edges, n=n)


def _balanced_random(rng, hg, k, eps):
    part = rng.integers(0, k, hg.n).astype(np.int32)
    return refine.rebalance(hg.vertex_weights, part, k, eps, rng)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.sampled_from([2, 4, 8]))
def test_lp_refine_monotone_and_balanced(seed, k):
    rng = np.random.default_rng(seed)
    hg = _rand_hg(rng, 48, 90)
    hga = hg.arrays()
    eps = 0.10
    part0 = _balanced_random(rng, hg, k, eps)
    cut0 = float(metrics.cutsize_jit(
        hga, refine.pad_part(part0, hga.n_pad), k))
    part1, cut1 = refine.lp_refine(hga, part0, k, eps, max_iters=6)
    assert cut1 <= cut0 + 1e-6
    assert bool(metrics.is_balanced(
        hga, refine.pad_part(part1, hga.n_pad), k, eps))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.sampled_from([2, 4]))
def test_fm_refine_monotone_and_balanced(seed, k):
    rng = np.random.default_rng(seed)
    hg = _rand_hg(rng, 32, 60)
    hga = hg.arrays()
    eps = 0.10
    part0 = _balanced_random(rng, hg, k, eps)
    cut0 = float(metrics.cutsize_jit(
        hga, refine.pad_part(part0, hga.n_pad), k))
    part1, cut1 = refine.fm_refine(hga, part0, k, eps, max_passes=2,
                                   step_budget=64)
    assert cut1 <= cut0 + 1e-6
    assert bool(metrics.is_balanced(
        hga, refine.pad_part(part1, hga.n_pad), k, eps))
    # reported cut must be the real cut
    assert cut1 == pytest.approx(float(metrics.cutsize_jit(
        hga, refine.pad_part(part1, hga.n_pad), k)))


def test_fm_improves_known_bad_partition():
    """Two cliques joined by one edge: FM from a mixed assignment must
    find the obvious 2-cut structure."""
    edges = []
    for c in (0, 1):
        base = c * 8
        for i in range(8):
            for j in range(i + 1, 8):
                edges.append([base + i, base + j])
    edges.append([3, 11])  # the single bridge
    hg = Hypergraph.from_edge_lists(edges, n=16)
    hga = hg.arrays()
    part0 = np.array([0, 1] * 8, np.int32)  # alternating = terrible
    # eps must leave headroom for one-vertex-at-a-time traversal (FM
    # enforces the cap strictly; eps=0.25 allows 9/16 transiently)
    part1, cut1 = refine.fm_refine(hga, part0, 2, eps=0.25)
    assert cut1 == pytest.approx(1.0)  # only the bridge is cut


def test_rebalance_fixes_overfull_blocks():
    rng = np.random.default_rng(3)
    hg = _rand_hg(rng, 40, 50)
    part = np.zeros(40, np.int32)  # everything in block 0
    fixed = refine.rebalance(hg.vertex_weights, part, 4, 0.05, rng)
    hga = hg.arrays()
    assert bool(metrics.is_balanced(
        hga, refine.pad_part(fixed, hga.n_pad), 4, 0.05))


# --------------------------------------------------------------------------
# FM gain routes: the dense incidence contraction against the segment-sums
# --------------------------------------------------------------------------
def _fm_level(seed, n=40, m=70, dup=False, pad_pins=None):
    """A random level with unit weights; ``dup`` lists the first pin of
    every net twice (multiplicity 2), ``pad_pins`` pads the pin tables
    with ghost pins."""
    rng = np.random.default_rng(seed)
    edges = [rng.choice(n, size=int(rng.integers(2, 6)), replace=False)
             for _ in range(m)]
    if dup:
        edges = [np.concatenate([e[:1], e]) for e in edges]
    hg = Hypergraph.from_edge_lists(edges, n=n)
    return hg, hg.arrays(pad_pins=pad_pins)


def _bad_parts(hg, k, eps, count, seed):
    rng = np.random.default_rng(seed)
    return np.stack([_balanced_random(rng, hg, k, eps)
                     for _ in range(count)])


def _fm_scalar(assemble, hg, hga, k=4, eps=0.1):
    part = refine.pad_part(_bad_parts(hg, k, eps, 1, 7)[0], hga.n_pad)
    cap = metrics.balance_cap(hga.total_weight, k, eps)
    return refine._fm_pass(hga, part, k, cap, 64, assemble=assemble)


def _fm_population(assemble, hg, hga, k=4, eps=0.1):
    """Each member on its own weight row: 1, 300, 2**20, and a mix that
    needs all three bf16 pieces (1,000,003 has 20 significant bits)."""
    rng = np.random.default_rng(11)
    rows = [np.full(hg.m, w, np.float32) for w in (1, 300, 2**20)]
    rows.append(rng.choice([1, 300, 2**20, 1_000_003], hg.m)
                .astype(np.float32))
    ew_pop = np.zeros((len(rows), hga.m_pad), np.float32)
    ew_pop[:, :hg.m] = rows
    parts = refine.pad_parts(_bad_parts(hg, k, eps, len(rows), 8),
                             hga.n_pad)
    cap = metrics.balance_cap(hga.total_weight, k, eps)
    return refine._fm_pass_population(hga, parts, k, cap, 64,
                                      edge_weights_pop=jnp.asarray(ew_pop),
                                      assemble=assemble)


def _fm_instances(assemble, hg, hga):
    """Two instances in one bucket: k=3 inside k_pad 4 (``k_live``), the
    second with an incumbent and a migration budget."""
    hg2, _ = _fm_level(5, n=30, m=50)
    batch = instances.stack_instances(
        [hga, hg2.arrays()], [3, 4], [0.1, 0.1],
        incumbents=[None, _bad_parts(hg2, 4, 0.1, 1, 3)[0]],
        mig_budgets=[None, 6.0])
    parts = instances.stack_parts(
        [_bad_parts(hg, 3, 0.1, 3, 9), _bad_parts(hg2, 4, 0.1, 3, 10)],
        batch.n_pad)
    return instances._fm_pass_instances(
        batch.hga, jnp.asarray(parts), k=batch.k_pad, cap=batch.cap,
        steps=batch.fm_steps, k_live=batch.k_live,
        incumbent=batch.incumbent, mig_budget=batch.mig_budget,
        assemble=assemble)


FM_ROUTE_CASES = {
    "scalar": (_fm_scalar, {}),
    "population_weights": (_fm_population, {}),
    "instances_klive_incumbent": (_fm_instances, {}),
    "multiplicity_2": (_fm_scalar, {"dup": True}),
    "ghost_pins": (_fm_population, {"pad_pins": 1024}),
}


@pytest.mark.parametrize("case", sorted(FM_ROUTE_CASES))
def test_fm_dense_route_bit_identical(case):
    run, level = FM_ROUTE_CASES[case]
    hg, hga = _fm_level(4, **level)
    if level.get("dup"):
        assert int(metrics.pin_multiplicity(hga)[hg.pins[0], 0]) == 2
    if level.get("pad_pins"):  # more ghost pins than bf16 counts exactly
        assert hga.p_pad - hg.num_pins > 256
    seg_parts, seg_cuts = run("segsum", hg, hga)
    den_parts, den_cuts = run("dense", hg, hga)
    np.testing.assert_array_equal(np.asarray(den_parts),
                                  np.asarray(seg_parts))
    np.testing.assert_array_equal(np.asarray(den_cuts),
                                  np.asarray(seg_cuts))


def test_fm_gain_route_resolution():
    route = partial(refine.fm_gain_route, 4096, 8192)
    assert route() == "segsum"                 # the tests run on the CPU
    assert route(backend="cpu") == "segsum"
    assert route(backend="tpu") == "dense"
    assert route(backend="tpu", pin_axis="model") == "segsum"
    # as many 64 MiB incidences as fill the budget, then one more
    fit = refine.fm_dense_budget() // (4096 * 8192 * 2)
    assert fit >= 1
    assert route(backend="tpu", lanes=fit) == "dense"
    assert route(backend="tpu", lanes=fit + 1) == "segsum"


@pytest.mark.parametrize("tier", ["population", "instances"])
def test_fm_dense_lanes_counter(tier, monkeypatch):
    hg, hga = _fm_level(6)
    parts = _bad_parts(hg, 4, 0.1, 3, 12)

    def solve():
        obs.reset()
        obs.enable()
        try:
            if tier == "population":
                out = refine.fm_refine_population(hga, parts, 4, 0.1,
                                                  max_passes=3)
            else:
                batch = instances.stack_instances([hga], [4], [0.1])
                out = instances.fm_refine_instances(
                    batch, instances.stack_parts([parts], batch.n_pad),
                    max_passes=3)
        finally:
            obs.disable()
        return out, obs.snapshot()["counters"]

    (seg_parts, seg_cuts), seg = solve()
    monkeypatch.setattr(refine, "fm_gain_route",
                        partial(refine.fm_gain_route, backend="tpu"))
    (den_parts, den_cuts), den = solve()
    obs.reset()
    assert seg["fm.lane_passes"] > 0
    assert seg.get("fm.dense_lanes", 0) == 0
    assert den["fm.dense_lanes"] == den["fm.lane_passes"]
    assert den["fm.lane_passes"] == seg["fm.lane_passes"]
    np.testing.assert_array_equal(den_parts, seg_parts)
    np.testing.assert_array_equal(den_cuts, seg_cuts)
