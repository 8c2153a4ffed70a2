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
The FM pass is compiled on both gain routes at the service cell's
coarse-level widths, and its move loop is searched for scatters.
"""
import os
import re

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


def _computations(hlo: str) -> dict:
    """HLO module text -> {computation name: its text}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return {n: "\n".join(body) for n, body in comps.items()}


def _reachable(comps: dict, root: str) -> str:
    """The text of ``root`` and of every computation it calls."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += re.findall(r"%([\w.\-]+)", " ".join(
            re.findall(r"(?:calls|body|condition|to_apply|"
                       r"branch_computations)=\{?([^}\s,]+(?:, *%[^}\s,]+)*)",
                       comps[name])))
    return "\n".join(comps[n] for n in seen)


def _fm_while_bodies(compiled) -> str:
    comps = _computations(compiled.as_text())
    bodies = set()
    for text in comps.values():
        bodies.update(re.findall(r"\bwhile\(.*body=%([\w.\-]+)", text))
    assert bodies, "no while loop in the compiled FM pass"
    return "\n".join(_reachable(comps, b) for b in bodies)


@pytest.mark.parametrize("assemble", ["dense", "segsum"])
def test_fm_pass_move_loop_for_v5e(assemble, one_chip, no_compile_cache):
    """The service's FM dispatch at the cell's widths (one ibm01 instance
    at the coarse levels: n_pad 4,096, m_pad 8,192, p_pad 16,384; k=4,
    alpha=7): on the dense route the move loop holds no scatter, and the
    program's temporaries fit the routing budget of one v5e."""
    from repro.core import instances, refine
    from repro.core.hypergraph import HypergraphArrays

    n_pad, m_pad, p_pad, alpha, k = 4096, 8192, 16384, 7, 4
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    hga = HypergraphArrays(
        pin_vertex=spec((1, p_pad), I32), pin_edge=spec((1, p_pad), I32),
        vertex_weights=spec((1, n_pad), F32),
        edge_weights=spec((1, m_pad), F32),
        edge_sizes=spec((1, m_pad), I32), n=spec((1,), I32),
        m=spec((1,), I32), incident=None)
    compiled = instances._fm_pass_instances.lower(
        hga, spec((1, alpha, n_pad), I32), k=k, cap=spec((1,), F32),
        steps=spec((1,), I32), k_live=spec((1,), I32),
        assemble=assemble).compile()
    loop = _fm_while_bodies(compiled)
    if assemble == "segsum":  # the check sees the scatters it forbids
        assert "scatter" in loop
        return
    assert "scatter" not in loop
    assert refine.fm_gain_route(n_pad, m_pad, backend="tpu") == "dense"
    temp = compiled.memory_analysis().temp_size_in_bytes
    v5e_budget = refine.FM_DENSE_MEM_SHARE * refine.DEVICE_BYTES_FALLBACK
    assert temp < v5e_budget
