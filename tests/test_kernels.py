"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret
mode executes the Pallas kernel bodies faithfully on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.connectivity import connectivity_pallas, cutsize_pallas
from repro.kernels.gain import gain_stream_pallas, gain_stream_batch_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas


@pytest.mark.parametrize("m,s,n,k", [
    (512, 8, 300, 2), (512, 16, 1000, 8), (1024, 32, 4096, 32),
    (512, 128, 512, 17),
])
def test_connectivity_sweep(m, s, n, k):
    rng = np.random.default_rng(m + s + k)
    pins = rng.integers(-1, n, size=(m, s)).astype(np.int32)
    part = rng.integers(0, k, size=n).astype(np.int32)
    got = connectivity_pallas(jnp.asarray(pins), jnp.asarray(part), k)
    want = ref.connectivity_ref(jnp.asarray(pins), jnp.asarray(part), k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,s,n,k,block_m", [
    (512, 8, 256, 4, 512), (2048, 16, 2048, 16, 512), (512, 8, 256, 4, 256),
])
def test_cutsize_sweep(m, s, n, k, block_m):
    rng = np.random.default_rng(m * k)
    pins = rng.integers(-1, n, size=(m, s)).astype(np.int32)
    part = rng.integers(0, k, size=n).astype(np.int32)
    w = rng.random(m).astype(np.float32)
    got = cutsize_pallas(jnp.asarray(pins), jnp.asarray(part),
                         jnp.asarray(w), k, block_m=block_m)
    want = ref.cutsize_ref(jnp.asarray(pins), jnp.asarray(part),
                           jnp.asarray(w), k)
    assert float(got) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("n,d,m,k", [
    (256, 8, 128, 4), (512, 16, 1024, 8), (256, 64, 300, 32),
])
def test_gain_gather_sweep(n, d, m, k):
    rng = np.random.default_rng(n + d)
    incident = rng.integers(-1, m, size=(n, d)).astype(np.int32)
    bi = rng.normal(size=(m, k)).astype(np.float32)
    wi = rng.normal(size=(m,)).astype(np.float32)
    got = gain_stream_pallas(jnp.asarray(incident), jnp.asarray(bi),
                             jnp.asarray(wi))
    want = ref.gain_gather_ref(jnp.asarray(incident), jnp.asarray(bi),
                               jnp.asarray(wi))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("alpha,n,d,m,k", [
    (1, 256, 8, 128, 4), (4, 512, 16, 300, 8), (7, 300, 8, 130, 5),
])
def test_gain_gather_batch_sweep(alpha, n, d, m, k):
    """Population-batched kernel == vmapped oracle, including shapes that
    are NOT multiples of the vertex block (internal padding)."""
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
                               rtol=1e-5, atol=1e-5)


def test_batch_kernel_matches_per_member_kernel():
    """Each slice of the batched launch equals the single-member kernel."""
    rng = np.random.default_rng(11)
    alpha, n, d, m, k = 3, 384, 8, 200, 6
    incident = rng.integers(-1, m, size=(n, d)).astype(np.int32)
    bi = rng.normal(size=(alpha, m, k)).astype(np.float32)
    wi = rng.normal(size=(alpha, m)).astype(np.float32)
    batched = np.asarray(gain_stream_batch_pallas(
        jnp.asarray(incident), jnp.asarray(bi), jnp.asarray(wi)))
    for a in range(alpha):
        single = np.asarray(gain_stream_pallas(
            jnp.asarray(incident), jnp.asarray(bi[a]), jnp.asarray(wi[a])))
        np.testing.assert_allclose(batched[a], single, rtol=1e-6, atol=1e-6)


def test_connectivity_odd_edge_count():
    """m that is not a multiple of block_m must work (internal padding
    replaced the old hard assert)."""
    rng = np.random.default_rng(7)
    m, s, n, k = 130, 8, 300, 5
    pins = rng.integers(-1, n, size=(m, s)).astype(np.int32)
    part = rng.integers(0, k, size=n).astype(np.int32)
    got = connectivity_pallas(jnp.asarray(pins), jnp.asarray(part), k)
    want = ref.connectivity_ref(jnp.asarray(pins), jnp.asarray(part), k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    w = rng.random(m).astype(np.float32)
    c = cutsize_pallas(jnp.asarray(pins), jnp.asarray(part),
                       jnp.asarray(w), k)
    cr = ref.cutsize_ref(jnp.asarray(pins), jnp.asarray(part),
                         jnp.asarray(w), k)
    assert float(c) == pytest.approx(float(cr), rel=1e-5)


def test_interpret_mode_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.interpret_mode() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.interpret_mode() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "auto")
    # this container runs on CPU -> interpreter
    assert ops.interpret_mode() is True


def test_interpret_mode_refused_on_device_backend(monkeypatch):
    """Forcing the interpreter on a compiled backend raises instead of
    silently running every kernel on the host."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        ops.interpret_mode()
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.interpret_mode() is False


@pytest.mark.parametrize("r,d,b,l,dtype,combiner", [
    (100, 16, 8, 4, jnp.float32, "sum"),
    (1000, 64, 32, 1, jnp.float32, "sum"),
    (500, 32, 16, 8, jnp.float32, "mean"),
    (100, 128, 8, 2, jnp.bfloat16, "sum"),
])
def test_embedding_bag_sweep(r, d, b, l, dtype, combiner):
    rng = np.random.default_rng(r + b)
    table = jnp.asarray(rng.normal(size=(r, d)).astype(np.float32), dtype)
    idx = rng.integers(-1, r, size=(b, l)).astype(np.int32)
    got = embedding_bag_pallas(table, jnp.asarray(idx), combiner=combiner)
    want = ref.embedding_bag_ref(table, jnp.asarray(idx), combiner=combiner)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_kernel_matches_core_metrics(small_hg):
    """Kernel layout path == CSR segment-sum path on a real netlist."""
    from repro.core import metrics, refine
    k = 8
    rng = np.random.default_rng(0)
    part = rng.integers(0, k, small_hg.n).astype(np.int32)
    pins = jnp.asarray(ops.edge_pin_matrix(small_hg))
    hga = small_hg.arrays()
    lam_kernel = np.asarray(ops.connectivity(
        pins, jnp.asarray(part), k))[: small_hg.m]
    lam_csr = np.asarray(metrics.connectivity_jit(
        hga, refine.pad_part(part, hga.n_pad), k))[: small_hg.m]
    np.testing.assert_array_equal(lam_kernel, lam_csr)
