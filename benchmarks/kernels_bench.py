"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp oracle vs the
CSR segment-sum path.  On CPU the interpret-mode timings are NOT TPU
timings — the meaningful outputs are the correctness deltas and the
bytes/flop footprints; wall times are recorded for regression tracking.

Also home of ``bench_coarsen`` (``BENCH_coarsen.json``): device-resident
vs host coarsening wall clock at n >= 1e5, with the host path charged
for the per-level host->device ship the device engine eliminates.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.core import metrics, refine
from repro.data.hypergraphs import titan_like


def _time(fn, reps=3):
    jax.block_until_ready(fn())  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6


def run(quick: bool = False, out=sys.stdout):
    hg = titan_like("neuron_like", scale=0.02 if quick else 0.05)
    k = 16
    rng = np.random.default_rng(0)
    part = rng.integers(0, k, hg.n).astype(np.int32)
    pins = jnp.asarray(ops.edge_pin_matrix(hg))
    hga = hg.arrays()
    padded = refine.pad_part(part, hga.n_pad)
    ew = jnp.zeros(pins.shape[0], jnp.float32
                   ).at[: hg.m].set(jnp.asarray(hg.edge_weights))

    print("table,name,us_per_call,derived", file=out)
    t_k = _time(lambda: ops.connectivity(pins, jnp.asarray(part), k))
    t_r = _time(lambda: ref.connectivity_ref(pins, jnp.asarray(part), k))
    t_csr = _time(lambda: metrics.connectivity_jit(hga, padded, k))
    same = bool((np.asarray(ops.connectivity(pins, jnp.asarray(part), k))
                 [: hg.m] ==
                 np.asarray(metrics.connectivity_jit(hga, padded, k))
                 [: hg.m]).all())
    print(f"kernels,connectivity_pallas,{t_k:.0f},exact={same}", file=out)
    print(f"kernels,connectivity_ref,{t_r:.0f},", file=out)
    print(f"kernels,connectivity_csr_xla,{t_csr:.0f},", file=out)

    t_c = _time(lambda: ops.cutsize(pins, jnp.asarray(part), ew, k))
    cut_k = float(ops.cutsize(pins, jnp.asarray(part), ew, k))
    cut_c = float(metrics.cutsize_jit(hga, padded, k))
    print(f"kernels,cutsize_pallas,{t_c:.0f},"
          f"delta={abs(cut_k - cut_c):.1e}", file=out)

    # population-batched gain kernel: one launch for alpha members vs
    # alpha single-member launches vs the vmapped XLA oracle
    alpha, kd = 7, 16
    n_inc, d_inc, m_inc = 512, 8, 256
    incident = jnp.asarray(
        rng.integers(-1, m_inc, size=(n_inc, d_inc)).astype(np.int32))
    bi = jnp.asarray(
        rng.normal(size=(alpha, m_inc, kd)).astype(np.float32))
    wi = jnp.asarray(rng.normal(size=(alpha, m_inc)).astype(np.float32))
    t_b = _time(lambda: ops.gain_gather_batch(incident, bi, wi))
    t_loop = _time(lambda: [ops.gain_gather(incident, bi[a], wi[a])
                            for a in range(alpha)])
    t_ref = _time(lambda: ref.gain_gather_batch_ref(incident, bi, wi))
    d_b = float(jnp.abs(ops.gain_gather_batch(incident, bi, wi)
                        - ref.gain_gather_batch_ref(incident, bi, wi)
                        ).max())
    print(f"kernels,gain_stream_batch_pallas_k{kd},{t_b:.0f},"
          f"maxerr={d_b:.1e}", file=out)
    print(f"kernels,gain_stream_looped_pallas_k{kd},{t_loop:.0f},"
          f"batch_speedup={t_loop / max(t_b, 1e-9):.2f}", file=out)
    print(f"kernels,gain_gather_batch_ref_k{kd},{t_ref:.0f},", file=out)

    # the same kernel above KERNEL_MAX_K: edge tables tiled over the
    # grid, partial gains accumulated in the resident output tile
    from repro.kernels.gain import (gain_stream_pallas,
                                    gain_stream_batch_pallas)
    ks = 48
    bi_s = jnp.asarray(rng.normal(size=(m_inc, ks)).astype(np.float32))
    wi_s = jnp.asarray(rng.normal(size=(m_inc,)).astype(np.float32))
    t_s = _time(lambda: gain_stream_pallas(incident, bi_s, wi_s))
    t_sr = _time(lambda: ref.gain_gather_ref(incident, bi_s, wi_s))
    d_s = float(jnp.abs(gain_stream_pallas(incident, bi_s, wi_s)
                        - ref.gain_gather_ref(incident, bi_s, wi_s)).max())
    print(f"kernels,gain_stream_pallas_k{ks},{t_s:.0f},maxerr={d_s:.1e}",
          file=out)
    print(f"kernels,gain_stream_ref_xla_k{ks},{t_sr:.0f},", file=out)
    bi_sb = jnp.asarray(
        rng.normal(size=(alpha, m_inc, ks)).astype(np.float32))
    wi_sb = jnp.asarray(rng.normal(size=(alpha, m_inc)).astype(np.float32))
    t_sb = _time(lambda: gain_stream_batch_pallas(incident, bi_sb, wi_sb))
    d_sb = float(jnp.abs(gain_stream_batch_pallas(incident, bi_sb, wi_sb)
                         - ref.gain_gather_batch_ref(incident, bi_sb, wi_sb)
                         ).max())
    print(f"kernels,gain_stream_batch_pallas_k{ks},{t_sb:.0f},"
          f"maxerr={d_sb:.1e}", file=out)

    # rating scatter kernel (device coarsener): sorted-segment sum via
    # one-hot MXU matmul vs the XLA segment-sum reference
    from repro.kernels.rating import rating_scatter_pallas
    C, S = 4096, 1024
    segs = jnp.asarray(np.sort(rng.integers(0, S, C)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=C).astype(np.float32))
    t_rp = _time(lambda: rating_scatter_pallas(vals, segs, S))
    t_rr = _time(lambda: ref.rating_segment_sum_ref(vals, segs, S))
    d_r = float(jnp.abs(rating_scatter_pallas(vals, segs, S)
                        - ref.rating_segment_sum_ref(vals, segs, S)).max())
    print(f"kernels,rating_scatter_pallas,{t_rp:.0f},maxerr={d_r:.1e}",
          file=out)
    print(f"kernels,rating_segment_sum_ref,{t_rr:.0f},", file=out)

    # interpret mode executes the (B, L) grid in Python — keep it tiny
    # (the TPU grid is sequential hardware DMA; size there is free)
    table = jnp.asarray(rng.normal(size=(10_000, 128)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 10_000, size=(16, 2)).astype(
        np.int32))
    t_e = _time(lambda: ops.embedding_bag(table, idx))
    t_er = _time(lambda: ref.embedding_bag_ref(table, idx))
    d = float(jnp.abs(ops.embedding_bag(table, idx)
                      - ref.embedding_bag_ref(table, idx)).max())
    print(f"kernels,embedding_bag_pallas,{t_e:.0f},maxerr={d:.1e}",
          file=out)
    print(f"kernels,embedding_bag_ref,{t_er:.0f},", file=out)


def bench_coarsen(quick: bool = False, out=sys.stdout,
                  json_path: str | None = "BENCH_coarsen.json",
                  scale: float | None = None, k: int = 64, reps: int = 2):
    """Device-resident vs host coarsening wall clock (BENCH_coarsen.json).

    Both engines build the full hierarchy ready for device refinement:
    the host path is therefore charged for its per-level ``arrays()``
    host->device conversion (the ship ``dcoarsen`` eliminates — its
    levels are born on device).  Default scale puts n >= 1e5, the regime
    the ISSUE tracks.  NOTE: on the CPU backend both engines run on the
    host and the XLA comparator sorts cannot beat numpy's run-aware
    timsort — those rows are a reference point; the ``auto`` coarsen
    path keeps the numpy engine on CPU and selects the device engine
    exactly where these numbers favour it (compiled backends, where the
    sorts/scatters run on-accelerator instead of round-tripping).
    """
    from repro.core import dcoarsen
    from repro.core.coarsen import coarsen

    scale = scale if scale is not None else (0.1 if quick else 3.4)
    hg = titan_like("gsm_switch_like", scale=scale)

    def host_path():
        h = hg.structural_copy()
        hier = coarsen(h, k, seed=7)
        for lv in hier.levels:
            lv.hg.arrays()          # the ship the device engine avoids
        jax.block_until_ready(hier.levels[-1].hg.arrays().pin_vertex)
        return hier

    def dev_path():
        h = hg.structural_copy()
        hier = dcoarsen.device_coarsen(h, k, seed=7)
        jax.block_until_ready(hier.levels[-1].hga.pin_vertex)
        return hier

    results = {}
    for name, fn in (("host", host_path), ("device", dev_path)):
        hier = fn()                 # warm-up / compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            hier = fn()
            best = min(best, time.perf_counter() - t0)
        results[name] = {"wall_s": best, "levels": hier.sizes()}

    speedup = results["host"]["wall_s"] / results["device"]["wall_s"]
    print("table,design,n,k,engine,wall_s,speedup", file=out)
    for name in ("host", "device"):
        print(f"coarsen,gsm_switch_like,{hg.n},{k},{name},"
              f"{results[name]['wall_s']:.2f},"
              f"{speedup if name == 'device' else 1.0:.2f}", file=out)
    record = {
        "bench": "coarsen_engine", "design": "gsm_switch_like",
        "n": hg.n, "m": hg.m, "pins": hg.num_pins, "k": k,
        "backend": jax.default_backend(),
        "interpret": ops.interpret_mode(),
        "rating_path": ops.rating_path(4 * hg.num_pins),
        "reps": reps,
        "host_wall_s": round(results["host"]["wall_s"], 3),
        "device_wall_s": round(results["device"]["wall_s"], 3),
        "device_speedup": round(speedup, 3),
        "host_levels": results["host"]["levels"],
        "device_levels": results["device"]["levels"],
        "note": ("CPU backend: reference point only — the auto coarsen "
                 "path keeps the host engine here; the device engine is "
                 "selected on compiled backends"
                 if jax.default_backend() == "cpu" else
                 "compiled backend: device engine is the auto path"),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path} (device speedup {speedup:.2f}x on "
              f"{record['backend']})", file=out)
    return record


if __name__ == "__main__":
    if "--coarsen" in sys.argv:
        bench_coarsen(quick="--quick" in sys.argv)
    else:
        run(quick="--quick" in sys.argv)
        bench_coarsen(quick="--quick" in sys.argv,
                      json_path=None if "--quick" in sys.argv
                      else "BENCH_coarsen.json")
