"""The device-resident gain engine (PR 2).

Three layers under test:

* kernel parity — ``gain_stream_pallas`` (edge-table tiling + VMEM
  accumulation) against the jnp oracles, across odd shapes, degree-0
  vertices, unit edges and large k;
* the dispatcher — ``ops.gain_path`` routing by k on every backend and the
  ``REPRO_GAIN_PATH`` override, plus all paths agreeing through
  ``metrics.gain_matrix``;
* the engine — the fused on-device LP attempt loop reproducing the
  scalar ``lp_refine`` trajectory bit-for-bit, and the per-level layout
  / placement caches actually caching.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import metrics, refine
from repro.core.hypergraph import Hypergraph
from repro.kernels import ops, ref
from repro.kernels.gain import gain_stream_pallas, gain_stream_batch_pallas


# --------------------------------------------------------------------------
# streaming kernel parity
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,m,k", [
    (256, 8, 128, 4),      # block-aligned
    (300, 8, 130, 5),      # n and m both off-block
    (256, 16, 1024, 40),   # k > KERNEL_MAX_K
    (100, 4, 50, 70),      # tiny m, large k
    (64, 8, 513, 3),       # m one past a block boundary
])
def test_gain_stream_parity(n, d, m, k):
    rng = np.random.default_rng(n + d + m + k)
    incident = rng.integers(-1, m, size=(n, d)).astype(np.int32)
    incident[:3] = -1                     # degree-0 vertices gather nothing
    bi = rng.normal(size=(m, k)).astype(np.float32)
    wi = rng.normal(size=(m,)).astype(np.float32)
    got = gain_stream_pallas(jnp.asarray(incident), jnp.asarray(bi),
                             jnp.asarray(wi))
    want = ref.gain_gather_ref(jnp.asarray(incident), jnp.asarray(bi),
                               jnp.asarray(wi))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gain_stream_matches_tile_order_oracle():
    """Bitwise: the kernel's per-tile accumulation equals the explicit
    tile-order oracle when the tile sizes line up."""
    rng = np.random.default_rng(0)
    n, d, m, k = 128, 8, 300, 6
    incident = rng.integers(-1, m, size=(n, d)).astype(np.int32)
    bi = rng.normal(size=(m, k)).astype(np.float32)
    wi = rng.normal(size=(m,)).astype(np.float32)
    got = gain_stream_pallas(jnp.asarray(incident), jnp.asarray(bi),
                             jnp.asarray(wi), block_m=128)
    want = ref.gain_stream_ref(jnp.asarray(incident), jnp.asarray(bi),
                               jnp.asarray(wi), block_m=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("alpha,n,d,m,k", [
    (1, 256, 8, 128, 4), (4, 300, 8, 515, 40), (7, 300, 8, 130, 5),
])
def test_gain_stream_batch_parity(alpha, n, d, m, k):
    rng = np.random.default_rng(alpha * n + d)
    incident = rng.integers(-1, m, size=(n, d)).astype(np.int32)
    bi = rng.normal(size=(alpha, m, k)).astype(np.float32)
    wi = rng.normal(size=(alpha, m)).astype(np.float32)
    got = gain_stream_batch_pallas(jnp.asarray(incident), jnp.asarray(bi),
                                   jnp.asarray(wi))
    want = ref.gain_gather_batch_ref(jnp.asarray(incident), jnp.asarray(bi),
                                     jnp.asarray(wi))
    assert got.shape == (alpha, n, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # member slices == single-member streaming launches, bit-for-bit
    for a in range(alpha):
        single = gain_stream_pallas(jnp.asarray(incident),
                                    jnp.asarray(bi[a]), jnp.asarray(wi[a]))
        np.testing.assert_array_equal(np.asarray(got[a]), np.asarray(single))


# --------------------------------------------------------------------------
# dispatcher routing
# --------------------------------------------------------------------------
def test_gain_path_routing(monkeypatch):
    monkeypatch.delenv("REPRO_GAIN_PATH", raising=False)
    # CPU container -> interpret mode -> XLA paths by k
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.gain_path(8) == "segsum"
    assert ops.gain_path(ops.KERNEL_MAX_K) == "segsum"
    assert ops.gain_path(ops.KERNEL_MAX_K + 1) == "compact"
    assert not ops.gain_layout_enabled()
    # compiled backend -> the same XLA paths: the kernel is slower there
    # and only runs when forced
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.gain_path(2) == "segsum"
    assert ops.gain_path(ops.KERNEL_MAX_K) == "segsum"
    assert ops.gain_path(64) == "compact"
    assert not ops.gain_layout_enabled()
    # explicit override wins
    monkeypatch.setenv("REPRO_GAIN_PATH", "compact")
    assert ops.gain_path(2) == "compact"
    assert not ops.gain_layout_enabled()
    monkeypatch.setenv("REPRO_GAIN_PATH", "stream")
    assert ops.gain_path(1024) == "stream"
    assert ops.gain_layout_enabled()
    # forced kernel without an incidence layout -> the XLA path for k
    assert ops.gain_path(8, incidence=False) == "segsum"
    assert ops.gain_path(64, incidence=False) == "compact"


def _random_hg(rng, n=60, m=110, unit_edges=True):
    edges = [rng.choice(n, size=int(rng.integers(2, 6)), replace=False)
             for _ in range(m - 2)]
    if unit_edges:
        edges += [[0], [int(rng.integers(0, n))]]   # size-1 edges
    else:
        edges += [rng.choice(n, size=2, replace=False) for _ in range(2)]
    w = rng.integers(1, 5, len(edges)).astype(np.float32)
    return Hypergraph.from_edge_lists(edges, n=n, edge_weights=w)


@pytest.mark.parametrize("k", [3, 8, 40, 70])
def test_compact_assembly_matches_segsum(k):
    """The sparse (<=2 nonzeros/edge) assembly is exact vs the reference
    segment-sum, including unit edges, size-2 edges and integer weights."""
    rng = np.random.default_rng(k)
    hg = _random_hg(rng)
    hga = hg.arrays()
    for seed in range(3):
        part = refine.pad_part(
            np.random.default_rng(seed).integers(0, k, hg.n).astype(np.int32),
            hga.n_pad)
        a = metrics.gain_matrix_jit(hga, part, k, assemble="segsum")
        b = metrics.gain_matrix_jit(hga, part, k, assemble="compact")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("k", [8, 40])
def test_gain_matrix_kernel_paths_end_to_end(k, monkeypatch):
    """gain_matrix / gain_matrix_population routed through the Pallas
    kernel (forced via env) match the segsum reference on a real
    hypergraph, scalar and population, below and above KERNEL_MAX_K."""
    monkeypatch.setenv("REPRO_GAIN_PATH", "stream")
    jax.clear_caches()
    try:
        rng = np.random.default_rng(11)
        hg = _random_hg(rng)
        hga = hg.arrays()
        assert hga.incident is not None       # layout attached when forced
        parts = jnp.stack([
            refine.pad_part(rng.integers(0, k, hg.n).astype(np.int32),
                            hga.n_pad) for _ in range(3)])
        want = np.asarray(metrics.gain_matrix_jit(
            hga, parts[0], k, assemble="segsum"))
        got = np.asarray(metrics.gain_matrix_jit(hga, parts[0], k))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        gotp = np.asarray(metrics.gain_matrix_population(hga, parts, k))
        # population slices bit-equal the scalar kernel path
        np.testing.assert_array_equal(gotp[0], got)
    finally:
        jax.clear_caches()                    # drop env-baked traces


# --------------------------------------------------------------------------
# fused on-device LP loop: scalar trajectory regression
# --------------------------------------------------------------------------
@pytest.mark.parametrize("k", [4, 40])
def test_fused_lp_loop_reproduces_scalar_trajectory(k):
    """lp_refine_population (one dispatch per round, on-device attempt
    loop) must be bit-for-bit the scalar lp_refine host loop — on the
    small-k segsum path AND the large-k compact path."""
    rng = np.random.default_rng(3 * k)
    hg = _random_hg(rng, n=120, m=260, unit_edges=False)
    hga = hg.arrays()
    eps = 0.10
    parts = [refine.rebalance(hg.vertex_weights,
                              rng.integers(0, k, hg.n).astype(np.int32),
                              k, eps) for _ in range(5)]
    ref_p, ref_c = [], []
    for p in parts:
        q, c = refine.lp_refine(hga, p.copy(), k, eps, max_iters=12)
        ref_p.append(np.asarray(q))
        ref_c.append(c)
    bat_p, bat_c = refine.lp_refine_population(
        hga, [p.copy() for p in parts], k, eps, max_iters=12)
    np.testing.assert_array_equal(np.asarray(ref_c), bat_c)
    for a in range(len(parts)):
        np.testing.assert_array_equal(ref_p[a], bat_p[a])


def test_fused_lp_loop_with_edge_weight_override(tiny_hg):
    """Mutation's biased-gain path threads through the fused loop: gains
    use the override weights, reported cuts stay true-weight."""
    k, eps = 4, 0.10
    hga = tiny_hg.arrays()
    rng = np.random.default_rng(1)
    ewo = jnp.asarray(
        np.concatenate([np.asarray(tiny_hg.edge_weights) * 3.0,
                        np.zeros(hga.m_pad - tiny_hg.m, np.float32)]))
    parts = [refine.rebalance(tiny_hg.vertex_weights,
                              rng.integers(0, k, tiny_hg.n).astype(np.int32),
                              k, eps) for _ in range(3)]
    ref_p, ref_c = [], []
    for p in parts:
        q, c = refine.lp_refine(hga, p.copy(), k, eps, max_iters=8,
                                edge_weight_override=ewo)
        ref_p.append(np.asarray(q))
        ref_c.append(c)
    bat_p, bat_c = refine.lp_refine_population(
        hga, [p.copy() for p in parts], k, eps, max_iters=8,
        edge_weight_override=ewo)
    np.testing.assert_array_equal(np.asarray(ref_c), bat_c)
    for a in range(len(parts)):
        np.testing.assert_array_equal(ref_p[a], bat_p[a])
    for a in range(len(parts)):   # reported cut is the TRUE cut
        assert bat_c[a] == pytest.approx(float(metrics.cutsize_jit(
            hga, jnp.asarray(bat_p[a]), k)))


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------
def test_arrays_and_layout_caches(tiny_hg):
    hga1 = tiny_hg.arrays()
    assert tiny_hg.arrays() is hga1                   # arrays() cached
    assert tiny_hg.arrays(pad_vertices=512) is not hga1
    inc1 = tiny_hg.incidence_matrix(256)
    assert tiny_hg.incidence_matrix(256) is inc1      # layout cached
    # reweighted copies share the structural layout cache
    hg2 = tiny_hg.with_edge_weights(
        np.asarray(tiny_hg.edge_weights) * 2.0)
    assert hg2.incidence_matrix(256) is inc1
    assert hg2.arrays() is not hga1                   # weights differ
    # ops-level helper goes through the same cache
    np.testing.assert_array_equal(ops.vertex_incidence_matrix(tiny_hg),
                                  inc1)


def test_fm_device_placement_cache(tiny_hg):
    hga = tiny_hg.arrays()
    dev = jax.local_devices()[0]
    p1 = refine._device_put_cached(hga, dev)
    p2 = refine._device_put_cached(hga, dev)
    assert p1 is p2                                   # no re-transfer
    other = tiny_hg.arrays(pad_vertices=512)
    assert refine._device_put_cached(other, dev) is not p1


def test_kernel_gate_constant():
    """The k-gate for the bitmask kernels is the shared named constant
    (was a magic 32 in two call sites): one uint32 lane bitmask."""
    from repro.kernels.common import (KERNEL_MAX_K, GAIN_STREAM_TILE_BYTES,
                                      VMEM_BUDGET_BYTES, stream_block_m)
    assert ops.KERNEL_MAX_K == KERNEL_MAX_K == np.iinfo(np.uint32).bits
    assert GAIN_STREAM_TILE_BYTES * 8 == VMEM_BUDGET_BYTES
    # the streamed [k, bm] table tile stays within its budget at any k
    for k in (2, KERNEL_MAX_K, 64, 1024):
        assert stream_block_m(k) * k * 4 <= GAIN_STREAM_TILE_BYTES
