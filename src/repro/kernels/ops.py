"""Jit'd public wrappers around the Pallas kernels + host layout helpers.

The partitioner's CSR arrays are re-blocked once per level into the padded
matrix layouts the kernels want (pins[M, S], incident[N, D]).  The
incidence layout is cached ON the host ``Hypergraph`` (see
``Hypergraph.incidence_matrix``), so it is built exactly once per level
and reused across every refinement round, population member and V-cycle
that revisits the level.

Interpreter mode is derived from the active backend: on CPU the Pallas
interpreter executes the kernel bodies faithfully; on TPU/GPU the real
kernels compile.  Override with ``REPRO_PALLAS_INTERPRET=0|1`` (anything
else, or unset, means auto); ``1`` is refused on a non-CPU backend, where
it would run every kernel on the host instead of the device.

Gain-path dispatch
------------------
``gain_path(k)`` picks how ``core.metrics.gain_matrix`` assembles the
[n, k] gain matrix from the per-edge tables, on every backend:

====================  =====================================================
path                  chosen when
====================  =====================================================
``"segsum"``          ``k <= KERNEL_MAX_K``: the XLA reference ([P, k]
                      per-pin segment-sum)
``"compact"``         ``k > KERNEL_MAX_K``: sparse XLA assembly exploiting
                      that ``becomes_internal`` has at most two nonzeros
                      per edge — O(P) scatter instead of O(P * k) (see
                      ``core.metrics.gain_matrix``)
``"stream"``          only when forced: ``gain_stream_pallas``, the edge
                      tables streamed over a second grid axis with partial
                      gains accumulated in the resident output tile
====================  =====================================================

The Pallas kernel compiles for the TPU but is off the automatic route
because it is slower there: it sweeps every (vertex tile, edge tile)
pair, O(n * m) work, where the XLA scatters are O(P * k).  On one TPU
v5e at ibm01's published size (n = 12,752, m = 14,111, 38,311 pins),
k = 64, alpha = 7, one population gain matrix took 91.6 ms on the
kernel against 11.8 ms on ``segsum`` and 14.9 ms on ``compact``.

``REPRO_GAIN_PATH=stream|segsum|compact`` forces a path (used by the
parity tests and the CI benchmark smoke); ``auto``/unset means the table
above.  The kernel path needs the dense incidence layout, which
``HypergraphArrays.from_host`` attaches only when the kernel is forced
(``gain_layout_enabled()``), so no run pays for a layout it never reads.
"""
from __future__ import annotations

import os

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.hypergraph import Hypergraph
from repro.env import warn_env_once
from . import ref
from .common import (GAIN_STREAM_TILE_BYTES,  # noqa: F401 (re-exported)
                     KERNEL_MAX_K, RATING_KERNEL_MAX_C, VMEM_BUDGET_BYTES)
from .connectivity import connectivity_pallas, cutsize_pallas
from .gain import gain_stream_pallas, gain_stream_batch_pallas
from .embedding_bag import embedding_bag_pallas
from .rating import rating_scatter_pallas, rating_scatter_batch_pallas

_INTERPRET_CACHE: bool | None = None


def interpret_mode() -> bool:
    """Whether Pallas kernels should run under the interpreter.

    Lazy (first call, not import) so importing this module never forces
    jax backend initialisation — launch/dryrun must set XLA flags first.
    """
    global _INTERPRET_CACHE
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "auto").strip().lower()
    if env in ("1", "true", "yes"):
        backend = jax.default_backend()
        if backend != "cpu":
            raise RuntimeError(
                f"REPRO_PALLAS_INTERPRET={env} on the {backend} backend "
                "would interpret every Pallas kernel on the host instead "
                "of running it on the device; unset it (or set 0/auto)")
        return True
    if env in ("0", "false", "no"):
        return False
    if env not in ("", "auto"):
        warn_env_once("REPRO_PALLAS_INTERPRET", env,
                      "auto (backend-detected)")
    if _INTERPRET_CACHE is None:
        _INTERPRET_CACHE = jax.default_backend() == "cpu"
    return _INTERPRET_CACHE


# --------------------------------------------------------------------------
# gain-path dispatch
# --------------------------------------------------------------------------
GAIN_PATHS = ("stream", "segsum", "compact")


def _gain_env() -> str:
    env = os.environ.get("REPRO_GAIN_PATH", "auto").strip().lower()
    if env not in GAIN_PATHS and env not in ("", "auto"):
        warn_env_once("REPRO_GAIN_PATH", env, "auto routing")
        return "auto"
    return env


def gain_layout_enabled() -> bool:
    """Should ``HypergraphArrays.from_host`` attach the dense incidence
    layout?  True iff the Pallas gain path is forced via
    ``REPRO_GAIN_PATH``."""
    return _gain_env() == "stream"


def gain_path(k: int, incidence: bool = True) -> str:
    """Resolve the gain-assembly path for ``k`` blocks (see module
    docstring for the decision table).  ``incidence``: whether the
    dense incidence layout is available — without it the forced kernel
    path is unreachable and the XLA path for ``k`` is used."""
    env = _gain_env()
    if env in ("segsum", "compact") or (env == "stream" and incidence):
        return env
    return "segsum" if k <= KERNEL_MAX_K else "compact"


def gain_assemble(incident: jnp.ndarray, becomes_internal: jnp.ndarray,
                  was_internal: jnp.ndarray, path: str) -> jnp.ndarray:
    """Kernel-path gain assembly (``path`` == "stream")."""
    if path == "stream":
        return gain_stream_pallas(incident, becomes_internal, was_internal,
                                  interpret=interpret_mode())
    raise ValueError(f"not a kernel gain path: {path!r}")


def gain_assemble_batch(incident: jnp.ndarray, becomes_internal: jnp.ndarray,
                        was_internal: jnp.ndarray, path: str) -> jnp.ndarray:
    """Population-batched kernel-path gain assembly."""
    if path == "stream":
        return gain_stream_batch_pallas(incident, becomes_internal,
                                        was_internal,
                                        interpret=interpret_mode())
    raise ValueError(f"not a kernel gain path: {path!r}")


# --------------------------------------------------------------------------
# rating-path dispatch (device coarsener, see core/dcoarsen)
# --------------------------------------------------------------------------
RATING_PATHS = ("pallas", "xla")


def rating_path(c: int) -> str:
    """How the device coarsener aggregates pair ratings for ``c``
    (padded) candidates: ``"pallas"`` — the MXU scatter kernel, chosen on
    compiled backends while its dense (segment x candidate) tile grid
    stays small (``c <= RATING_KERNEL_MAX_C``, the coarse/mid rounds) —
    or ``"xla"`` — the linear segment-sum, CPU / interpret / fine rounds.
    ``REPRO_RATING_PATH=pallas|xla`` forces it (parity tests / smoke)."""
    env = os.environ.get("REPRO_RATING_PATH", "auto").strip().lower()
    if env in RATING_PATHS:
        return env
    if env not in ("", "auto"):
        warn_env_once("REPRO_RATING_PATH", env, "auto routing")
    if interpret_mode() or c > RATING_KERNEL_MAX_C:
        return "xla"
    return "pallas"


def rating_segment_sum(vals: jnp.ndarray, segs: jnp.ndarray,
                       num_segments: int) -> jnp.ndarray:
    """Segment-sum of candidate-pair ratings by SORTED segment id
    (ids < 0 are dropped), routed by ``rating_path()``."""
    if rating_path(vals.shape[0]) == "pallas":
        return rating_scatter_pallas(vals, segs, num_segments,
                                     interpret=interpret_mode())
    return ref.rating_segment_sum_ref(vals, segs, num_segments)


def rating_segment_sum_batch(vals: jnp.ndarray, segs: jnp.ndarray,
                             num_segments: int) -> jnp.ndarray:
    """Population-batched rating aggregation for the mutation cohort
    (DESIGN.md §10): ``vals`` [alpha, C] per-member candidate ratings
    over one SHARED sorted segment structure ``segs`` [C].  Routed by
    ``rating_path()`` on the shared candidate count — the batch kernel
    mirrors the scalar kernel's tile program per lane, the XLA fallback
    vmaps the scalar segment-sum, so each member's row is bit-equal to
    its own ``rating_segment_sum`` call on either path."""
    if rating_path(vals.shape[1]) == "pallas":
        return rating_scatter_batch_pallas(vals, segs, num_segments,
                                           interpret=interpret_mode())
    return ref.rating_segment_sum_batch_ref(vals, segs, num_segments)


# --------------------------------------------------------------------------
# host layout converters
# --------------------------------------------------------------------------
def edge_pin_matrix(hg: Hypergraph, block_m: int = 512,
                    lane_pad: int = 8) -> np.ndarray:
    """CSR -> padded [M_pad, S_pad] pin matrix (pad = -1)."""
    from repro.core.hypergraph import _round_pow2
    sizes = hg.edge_sizes()
    s_pad = max(int(_round_pow2(int(sizes.max()) if hg.m else 1, lane_pad)), lane_pad)
    m_pad = ((hg.m + block_m - 1) // block_m) * block_m
    out = np.full((m_pad, s_pad), -1, np.int32)
    rows = hg.pin_edge_ids()
    cols = (np.arange(hg.num_pins, dtype=np.int64)
            - np.repeat(hg.edge_offsets[:-1], sizes))
    out[rows, cols] = hg.pins
    return out


def vertex_incidence_matrix(hg: Hypergraph, block_n: int = 256,
                            lane_pad: int = 8) -> np.ndarray:
    """dual CSR -> padded [N_pad, D_pad] incident-edge matrix (pad = -1).

    Delegates to the per-level cache on ``hg`` — repeated calls (rounds,
    members, V-cycles) return the same array without rebuilding.
    """
    n_rows = ((hg.n + block_n - 1) // block_n) * block_n
    return hg.incidence_matrix(max(n_rows, block_n), lane_pad=lane_pad)


# --------------------------------------------------------------------------
# public ops (kernel or oracle, same signature)
# --------------------------------------------------------------------------
def connectivity(pins: jnp.ndarray, part: jnp.ndarray, k: int,
                 use_kernel: bool = True) -> jnp.ndarray:
    if use_kernel and k <= KERNEL_MAX_K:
        return connectivity_pallas(pins, part, k,
                                   interpret=interpret_mode())
    return ref.connectivity_ref(pins, part, k)


def cutsize(pins: jnp.ndarray, part: jnp.ndarray, edge_weights: jnp.ndarray,
            k: int, use_kernel: bool = True) -> jnp.ndarray:
    if use_kernel and k <= KERNEL_MAX_K:
        return cutsize_pallas(pins, part, edge_weights, k,
                              interpret=interpret_mode())
    return ref.cutsize_ref(pins, part, edge_weights, k)


def edge_terms(phi: jnp.ndarray, edge_sizes: jnp.ndarray,
               edge_weights: jnp.ndarray):
    """Per-edge FM terms from Phi (stage 1 of the gain pipeline)."""
    sizes = edge_sizes[:, None]
    w = edge_weights[:, None]
    becomes_internal = jnp.where(phi == sizes - 1, w, 0.0)
    was_internal = jnp.where((phi == sizes) & (sizes > 0), w, 0.0).sum(-1)
    return becomes_internal, was_internal


def gain_gather(incident: jnp.ndarray, becomes_internal: jnp.ndarray,
                was_internal: jnp.ndarray, use_kernel: bool = True
                ) -> jnp.ndarray:
    if use_kernel:
        return gain_stream_pallas(incident, becomes_internal, was_internal,
                                  interpret=interpret_mode())
    return ref.gain_gather_ref(incident, becomes_internal, was_internal)


def gain_gather_batch(incident: jnp.ndarray, becomes_internal: jnp.ndarray,
                      was_internal: jnp.ndarray, use_kernel: bool = True
                      ) -> jnp.ndarray:
    """Population-batched gain assembly: one launch for all alpha members
    (shared incidence tile, per-member edge tables).

    incident [N, D]; becomes_internal [alpha, M, k]; was_internal
    [alpha, M] -> gains [alpha, N, k].
    """
    if use_kernel:
        return gain_stream_batch_pallas(incident, becomes_internal,
                                        was_internal,
                                        interpret=interpret_mode())
    return ref.gain_gather_batch_ref(incident, becomes_internal,
                                     was_internal)


def embedding_bag(table: jnp.ndarray, indices: jnp.ndarray,
                  combiner: str = "sum", use_kernel: bool = True
                  ) -> jnp.ndarray:
    if use_kernel:
        return embedding_bag_pallas(table, indices, combiner=combiner,
                                    interpret=interpret_mode())
    return ref.embedding_bag_ref(table, indices, combiner=combiner)
