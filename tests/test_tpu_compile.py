"""Ahead-of-time compiles, for a described TPU v5e, of every Pallas
kernel the TPU can be routed to — the rating kernel on the automatic
route, the gain kernel when ``REPRO_GAIN_PATH=stream`` forces it — at
the widths ``chip_smoke.py`` drives.

Nothing runs: the TPU compiler that ships with jax compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned block shapes, gathers it cannot lower, VMEM overruns) — the
failures interpret mode cannot show.  The topology is described inside
a module fixture, never at import, so every xdist worker collects the
same tests and only the worker running this file loads the TPU library.

Widths: the solve is ibm01 at its published size (n = 12,752 -> n_pad
16,384; m = 14,111 -> m_pad 16,384; max degree 12 -> D = 16) at k = 64,
alpha = 7 (and k = 32); the service requests (n <= 900, k <= 8,
alpha = 4) at small tiles; the rating kernel is compiled at its routing bound
``RATING_KERNEL_MAX_C`` candidates for one member and a cohort of 7.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.gain import gain_stream_batch_pallas, gain_stream_pallas
from repro.kernels.rating import (rating_scatter_batch_pallas,
                                  rating_scatter_pallas)

F32, I32 = jnp.float32, jnp.int32
C_MAX = ops.RATING_KERNEL_MAX_C

# name -> (kernel, [(shape, dtype) per array argument], static kwargs)
CASES = {
    "gain_stream": (gain_stream_pallas,
                    [((16384, 16), I32), ((16384, 64), F32),
                     ((16384,), F32)], {}),
    "gain_stream_batch": (gain_stream_batch_pallas,
                          [((16384, 16), I32), ((7, 16384, 64), F32),
                           ((7, 16384), F32)], {}),
    "gain_stream_service": (gain_stream_pallas,
                            [((1024, 16), I32), ((2048, 8), F32),
                             ((2048,), F32)], {}),
    "gain_stream_batch_service": (gain_stream_batch_pallas,
                                  [((1024, 16), I32), ((4, 2048, 8), F32),
                                   ((4, 2048), F32)], {}),
    "gain_stream_batch_kmax": (
        gain_stream_batch_pallas,
        [((16384, 16), I32), ((7, 16384, ops.KERNEL_MAX_K), F32),
         ((7, 16384), F32)], {}),
    "rating_scatter": (rating_scatter_pallas,
                       [((C_MAX,), F32), ((C_MAX,), I32)],
                       {"num_segments": C_MAX}),
    "rating_scatter_batch": (rating_scatter_batch_pallas,
                             [((7, C_MAX), F32), ((C_MAX,), I32)],
                             {"num_segments": C_MAX}),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip; keep these out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    kernel, shapes, static = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    fn = jax.jit(lambda *a: kernel(*a, interpret=False, **static))
    compiled = fn.lower(*args).compile()
    # a Mosaic kernel, not an XLA fallback
    assert "tpu_custom_call" in compiled.as_text()
