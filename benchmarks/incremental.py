"""Incremental repartitioning benchmark (``BENCH_incremental.json``).

Replays a crc32-seeded ``drift_stream`` over a modular netlist and, at
every step, solves the drifted instance twice: **warm** through
``incremental_partition`` (incumbent = previous step's answer, hierarchy
replayed through the shared ``IncrementalState``) and **cold** through
the service's ``solve_solo`` pipeline (full rebuild from random seeds —
what the engine did before DESIGN.md §14).

Every row is validated BEFORE it is written: both parts in range and
balanced, both cuts recomputed from the parts and asserted equal to the
reported cuts, and the warm answer's migration ≤ its budget.  The
summary asserts the acceptance criteria outright — warm beats cold on
mean wall clock at equal-or-better mean cut — so a stale JSON cannot
claim a win the run did not measure.

``--smoke`` shrinks sizes for CI; ``--json-dir DIR`` redirects the
record (workflow artifact trail).  Like ``benchmarks/service.py``, the
opposite device topology runs in a subprocess with
``--xla_force_host_platform_device_count`` forced, so the JSON always
carries a single-device and a multi-device row set.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _validate_part(hg, part, k, eps, cut, tag):
    """Hard validity gate: blocks in range, balance under cap, reported
    cut equal to the cut recomputed from the part."""
    from repro.core import metrics, refine
    part = np.asarray(part)
    if part.shape != (hg.n,):
        raise RuntimeError(f"{tag}: bad part shape {part.shape}")
    if part.min() < 0 or part.max() >= k:
        raise RuntimeError(f"{tag}: block ids out of range")
    vw = np.asarray(hg.vertex_weights, np.float64)
    cap = float(np.ceil(vw.sum() / k)) * (1.0 + eps)
    load = float(np.bincount(part, weights=vw, minlength=k).max())
    if load > cap * (1 + 1e-5) + 1e-6:
        raise RuntimeError(f"{tag}: balance cap exceeded ({load} > {cap})")
    hga = hg.arrays()
    recut = float(metrics.cutsize(hga, refine.pad_part(part, hga.n_pad),
                                  k))
    if abs(recut - float(cut)) > 1e-3:
        raise RuntimeError(f"{tag}: reported cut {cut} != recomputed "
                           f"{recut}")


def measure_rows(steps: int, scale: float, k: int = 8,
                 migration_frac: float = 0.15, magnitude: float = 0.15,
                 shard=None, out=sys.stdout):
    """Warm-vs-cold rows over one drift stream on the current topology."""
    import jax
    from repro.core import popshard
    from repro.core.incremental import (IncrementalConfig,
                                        IncrementalState,
                                        incremental_partition)
    from repro.data.hypergraphs import _modular_netlist, drift_stream
    from repro.serve.partition_service import (PartitionRequest,
                                               PartitionService)

    n, m = max(int(1500 * scale), 256), max(int(2000 * scale), 384)
    base = _modular_netlist(n, m, seed=77, n_modules=max(n // 64, 8),
                            p_local=0.8, fanout_tail=1.5)
    eps = 0.08
    svc = PartitionService(slots=1, shard=shard)
    cfg = IncrementalConfig(k=k, eps=eps, alpha=4,
                            migration_frac=migration_frac, seed=0,
                            pop_shard=shard)
    state = IncrementalState()

    # initial placement + compile warm-up for BOTH arms (untimed): the
    # cold solve compiles the scratch pipeline, the incremental solve
    # builds the resident hierarchy and compiles the warm pipeline
    part0, _ = svc.solve_solo(PartitionRequest("base", base, k, eps=eps))
    incumbent = np.asarray(part0, np.int32)
    incremental_partition(base, incumbent, cfg, state=state)

    stream = drift_stream(base, steps, magnitude=magnitude,
                          tag="bench-incr")
    vw = np.asarray(base.vertex_weights, np.float64)
    rows = []
    for i, hg_t in enumerate(stream):
        t0 = time.perf_counter()
        cold_part, cold_cut = svc.solve_solo(
            PartitionRequest(f"cold-{i}", hg_t, k, eps=eps))
        t_cold = time.perf_counter() - t0

        t0 = time.perf_counter()
        res = incremental_partition(hg_t, incumbent, cfg, state=state)
        t_warm = time.perf_counter() - t0

        # validity gates run before ANY row is recorded
        _validate_part(hg_t, cold_part, k, eps, cold_cut,
                       f"cold step {i}")
        _validate_part(hg_t, res.part, k, eps, res.cut,
                       f"warm step {i}")
        moved = float(vw[np.asarray(res.part) != incumbent].sum())
        if moved > res.budget_weight + 1e-4:
            raise RuntimeError(
                f"warm step {i}: migration {moved} exceeds budget "
                f"{res.budget_weight}")
        if abs(moved - res.migration_weight) > 1e-4:
            raise RuntimeError(
                f"warm step {i}: reported migration "
                f"{res.migration_weight} != measured {moved}")

        rows.append({
            "step": i, "warm_s": round(t_warm, 4),
            "cold_s": round(t_cold, 4),
            "warm_cut": float(res.cut), "cold_cut": float(cold_cut),
            "migration_weight": round(moved, 2),
            "budget_weight": round(float(res.budget_weight), 2),
            "migration_within_budget": True,
            "hierarchy": res.reused,
        })
        print(f"incremental,step={i},warm={t_warm:.3f}s,"
              f"cold={t_cold:.3f}s,warm_cut={res.cut:.0f},"
              f"cold_cut={cold_cut:.0f},mig={moved:.0f}/"
              f"{res.budget_weight:.0f},hier={res.reused}", file=out)
        incumbent = np.asarray(res.part, np.int32)

    warm_s = float(np.mean([r["warm_s"] for r in rows]))
    cold_s = float(np.mean([r["cold_s"] for r in rows]))
    warm_cut = float(np.mean([r["warm_cut"] for r in rows]))
    cold_cut_m = float(np.mean([r["cold_cut"] for r in rows]))
    if warm_s >= cold_s:
        raise RuntimeError(
            f"warm start did not beat from-scratch on wall clock: "
            f"{warm_s:.3f}s vs {cold_s:.3f}s")
    if warm_cut > cold_cut_m:
        raise RuntimeError(
            f"warm mean cut {warm_cut:.1f} worse than cold "
            f"{cold_cut_m:.1f} — not an equal-or-better-cut win")
    summary = {
        "mean_warm_s": round(warm_s, 4), "mean_cold_s": round(cold_s, 4),
        "speedup": round(cold_s / warm_s, 3),
        "mean_warm_cut": round(warm_cut, 2),
        "mean_cold_cut": round(cold_cut_m, 2),
        "cut_ratio_warm_over_cold": round(warm_cut / cold_cut_m, 4),
        "all_within_budget": True,
    }
    print(f"incremental,summary,speedup={summary['speedup']}x,"
          f"cut_ratio={summary['cut_ratio_warm_over_cold']}", file=out)
    return {"devices": len(jax.local_devices()),
            "backend": jax.default_backend(),
            "shard_path": popshard.resolve(shard),
            "rows": rows, "summary": summary}


def _rows_subprocess(ndev: int, steps: int, scale: float,
                     out=sys.stdout):
    """The same measurement in a fresh process with ``ndev`` forced host
    devices (progress on stderr, JSON record on stdout)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), _REPO])
    code = (
        "import json, sys\n"
        "from benchmarks.incremental import measure_rows\n"
        f"r = measure_rows({steps}, {scale!r}, out=sys.stderr)\n"
        "print(json.dumps(r))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"forced-{ndev}-device incremental run failed:\n"
            f"{proc.stderr}")
    print(f"# forced {ndev}-device subprocess done", file=out)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_incremental(smoke: bool = False, out=sys.stdout,
                      json_path: str | None = "BENCH_incremental.json"):
    """Emit BENCH_incremental.json (schema: docs/reference.md)."""
    import jax
    if smoke:
        steps, scale = 3, 0.3
    else:
        steps, scale = 8, 1.0
    ndev = len(jax.local_devices())
    local = measure_rows(steps, scale, out=out)
    forced = None
    if jax.default_backend() == "cpu":
        other = 8 if ndev == 1 else 1
        forced = _rows_subprocess(other, steps, scale, out=out)
    else:
        # this process holds the device: a child could not reach it, and
        # forced host-CPU rows would pass for device rows
        print(f"# {jax.default_backend()} backend: forced-host-device "
              "rows skipped", file=out)
    single = local if local["devices"] == 1 else forced
    multi = forced if single is local else local
    record = {
        "bench": "incremental",
        "steps": steps, "scale": scale, "k": 8,
        "migration_frac": 0.15, "drift_magnitude": 0.15,
        "alpha": 4, "lp_iters": 8,
        "single_device": single,
        "multi_device": multi,
        "note": ("warm = incremental_partition with hierarchy replay + "
                 "incumbent seeding + bounded migration; cold = the "
                 "service's from-scratch solve_solo pipeline on the same "
                 "drifted instance.  Rows only exist because the "
                 "validity gates passed: parts in range + balanced, "
                 "cuts recomputed and equal, migration <= budget on "
                 "every row, and the summary asserts mean warm wall < "
                 "mean cold wall at mean warm cut <= mean cold cut.  "
                 "Forced host devices oversubscribe CPU cores, so the "
                 "multi-device rows track dispatch correctness, not a "
                 "speedup (docs/reference.md caveats)."),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        speedups = ", ".join(f"{rows['summary']['speedup']}x {label}"
                             for label, rows in (("single", single),
                                                 ("multi", multi))
                             if rows is not None)
        print(f"# wrote {json_path} (speedup={speedups})", file=out)
    return record


if __name__ == "__main__":
    json_dir = None
    if "--json-dir" in sys.argv:
        i = sys.argv.index("--json-dir") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("--json-dir requires a directory argument")
        json_dir = sys.argv[i]
        os.makedirs(json_dir, exist_ok=True)
    jp = ("BENCH_incremental.json" if json_dir is None
          else os.path.join(json_dir, "BENCH_incremental.json"))
    bench_incremental(smoke="--smoke" in sys.argv, json_path=jp)
