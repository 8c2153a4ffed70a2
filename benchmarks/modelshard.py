"""Model-axis structure sharding benchmark (``BENCH_modelshard.json``).

The acceptance record for DESIGN.md §15: a giant instance (n >= 1e6,
``giant_netlist``) whose structure arrays exceed an artificial
per-device memory budget (``REPRO_DEVICE_MEM_BUDGET``, set between the
1-way and the model-sharded per-device footprints) must

* FAIL the unsharded dispatch with ``DeviceBudgetExceeded`` — the
  "this instance OOMs on one device" arm, provable on forced host
  devices where no real HBM limit exists; and
* COMPLETE end-to-end with ``REPRO_MODEL_SHARD=mesh`` — the pin tables
  row-sharded over the mesh's "model" axis, segment-sums psum'd.

Every row is validated before it is written: the sharded run's
reported cuts are recomputed from the returned partitions, and a
moderate-size parity gate asserts the model-sharded engine bit-equal
to the replicated one on the same workload.  The measurement runs in a
subprocess with 8 forced host devices and ``REPRO_POP_MESH_MODEL=2``
(pop 4 x model 2), so the JSON carries a real model axis regardless of
the parent topology.

``--smoke`` shrinks the refinement work (not the instance — the
n >= 1e6 budget arithmetic IS the bench); ``--json-dir DIR`` redirects
the record (workflow artifact trail).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_GIANT, M_GIANT = 1_000_000, 1_300_000


def measure_rows(n: int, m: int, k: int = 8, alpha: int = 2,
                 max_iters: int = 1, out=sys.stdout):
    """The unsharded-fails / sharded-completes pair plus the parity
    gate, on the CURRENT topology (expects a real model axis and
    ``REPRO_DEVICE_MEM_BUDGET`` pinned between the two footprints)."""
    import jax
    from repro.core import metrics, popshard, refine
    from repro.data.hypergraphs import _modular_netlist, giant_netlist

    mesh = popshard.pop_mesh()
    nmodel = mesh.shape["model"]
    if nmodel < 2:
        raise RuntimeError(f"model axis is {nmodel}; the bench needs "
                           "REPRO_POP_MESH_MODEL >= 2")
    budget = popshard.device_mem_budget()
    if budget is None:
        raise RuntimeError("REPRO_DEVICE_MEM_BUDGET unset; the OOM arm "
                           "would be vacuous")

    t0 = time.perf_counter()
    hg = giant_netlist(n, m, seed=5)
    hga = hg.arrays()
    t_build = time.perf_counter() - t0
    bytes_1way = popshard.structure_bytes_per_device(hga, 1)
    bytes_shard = popshard.structure_bytes_per_device(hga, nmodel)
    if not bytes_shard <= budget < bytes_1way:
        raise RuntimeError(
            f"budget {budget} does not discriminate: 1-way {bytes_1way}, "
            f"{nmodel}-way {bytes_shard}")
    print(f"modelshard,instance,n={n},m={m},pins={hg.num_pins},"
          f"build={t_build:.2f}s,bytes_1way={bytes_1way},"
          f"bytes_{nmodel}way={bytes_shard},budget={budget}", file=out)

    # balanced block warm starts (unit weights): no host rebalance pass
    base = (np.arange(n, dtype=np.int64) * k // n).astype(np.int32)
    parts = [np.roll(base, 977 * a) for a in range(alpha)]
    cut_seed = float(metrics.cutsize_jit(
        hga, refine.pad_part(base, hga.n_pad), k))

    # arm 1: the unsharded dispatch must trip the budget
    t0 = time.perf_counter()
    try:
        refine.lp_refine_population(hga, [p.copy() for p in parts], k,
                                    0.05, max_iters=max_iters,
                                    shard="mesh", model_shard="off")
        raise RuntimeError("unsharded dispatch fit under the budget — "
                           "the OOM arm did not fire")
    except popshard.DeviceBudgetExceeded as e:
        row_oom = {"path": "unsharded", "completed": False,
                   "error": "DeviceBudgetExceeded", "detail": str(e),
                   "bytes_per_device": bytes_1way, "budget": budget,
                   "wall_s": round(time.perf_counter() - t0, 4)}
    print(f"modelshard,unsharded,oom=DeviceBudgetExceeded", file=out)

    # arm 2: the model-sharded dispatch completes end-to-end
    t0 = time.perf_counter()
    out_parts, cuts = refine.lp_refine_population(
        hga, [p.copy() for p in parts], k, 0.05, max_iters=max_iters,
        shard="mesh", model_shard="mesh")
    t_shard = time.perf_counter() - t0
    out_parts = np.asarray(out_parts)
    recut = float(metrics.cutsize_jit(
        hga, refine.pad_part(out_parts[0, :n], hga.n_pad), k))
    if recut != float(cuts[0]):
        raise RuntimeError(f"reported cut {float(cuts[0])} != recomputed "
                           f"{recut}")
    if float(cuts[0]) > cut_seed:
        raise RuntimeError("sharded refinement worsened the seed cut")
    row_shard = {"path": "model-sharded", "completed": True,
                 "nmodel": nmodel, "bytes_per_device": bytes_shard,
                 "budget": budget, "wall_s": round(t_shard, 4),
                 "cut_seed": cut_seed, "cut": float(cuts[0]),
                 "cut_recomputed_equal": True}
    print(f"modelshard,sharded,wall={t_shard:.2f}s,cut={float(cuts[0]):.0f}"
          f" (seed {cut_seed:.0f})", file=out)

    # parity gate (moderate size, budget-free): mesh bit-equal to off
    os.environ.pop("REPRO_DEVICE_MEM_BUDGET", None)
    phg = _modular_netlist(600, 800, seed=11, n_modules=8, p_local=0.8,
                           fanout_tail=1.5)
    phga = phg.arrays()
    rng = np.random.default_rng(3)
    pparts = [refine.rebalance(phg.vertex_weights,
                               rng.integers(0, k, phg.n).astype(np.int32),
                               k, 0.08) for _ in range(4)]
    res = {ms: refine.refine_population(
        phga, [q.copy() for q in pparts], k, 0.08, max_iters=4,
        shard="mesh", model_shard=ms) for ms in ("off", "mesh")}
    if not (np.array_equal(np.asarray(res["mesh"][0]),
                           np.asarray(res["off"][0]))
            and np.array_equal(np.asarray(res["mesh"][1]),
                               np.asarray(res["off"][1]))):
        raise RuntimeError("model-shard parity gate failed: mesh != off")
    print("modelshard,parity,ok", file=out)

    return {"devices": len(jax.local_devices()),
            "backend": jax.default_backend(),
            "mesh": dict(mesh.shape),
            "n": n, "m": m, "pins": int(hg.num_pins),
            "k": k, "alpha": alpha, "max_iters": max_iters,
            "build_s": round(t_build, 4),
            "rows": [row_oom, row_shard],
            "parity_gate": {"n": phg.n, "bit_equal": True}}


def _rows_subprocess(n: int, m: int, alpha: int, max_iters: int,
                     budget: int, out=sys.stdout):
    """Run the measurement with 8 forced host devices, a 2-sized model
    axis and the discriminating budget pinned."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(_REPO, "src"),
                                         _REPO])
    env["REPRO_POP_MESH_MODEL"] = "2"
    env["REPRO_DEVICE_MEM_BUDGET"] = str(budget)
    env.pop("REPRO_POP_SHARD", None)
    env.pop("REPRO_MODEL_SHARD", None)
    code = (
        "import json, sys\n"
        "from benchmarks.modelshard import measure_rows\n"
        f"r = measure_rows({n}, {m}, alpha={alpha}, "
        f"max_iters={max_iters}, out=sys.stderr)\n"
        "print(json.dumps(r))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"forced-8-device modelshard run failed:\n"
                           f"{proc.stderr}")
    for line in proc.stderr.splitlines():
        if line.startswith("modelshard,"):
            print(line, file=out)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_modelshard(smoke: bool = False, out=sys.stdout,
                     json_path: str | None = "BENCH_modelshard.json"):
    """Emit BENCH_modelshard.json (schema: docs/reference.md)."""
    import jax
    alpha, max_iters = (2, 1) if smoke else (4, 2)
    budget = 45 * 1024 * 1024   # between ~54.5 MB 1-way and ~37.7 MB 2-way
    backend = jax.default_backend()
    forced = local = None
    if backend == "cpu":
        res = forced = _rows_subprocess(N_GIANT, M_GIANT, alpha, max_iters,
                                        budget, out=out)
    else:
        # this process holds the devices: a child could not reach them,
        # and forced host-CPU rows would pass for device rows
        print(f"# {backend} backend: forced-host-device rows skipped",
              file=out)
        if len(jax.local_devices()) < 2:
            raise SystemExit("modelshard needs >= 2 local devices for a "
                             "model axis of 2")
        os.environ["REPRO_POP_MESH_MODEL"] = "2"
        os.environ["REPRO_DEVICE_MEM_BUDGET"] = str(budget)
        os.environ.pop("REPRO_POP_SHARD", None)
        os.environ.pop("REPRO_MODEL_SHARD", None)
        res = local = measure_rows(N_GIANT, M_GIANT, alpha=alpha,
                                   max_iters=max_iters, out=out)
    record = {
        "bench": "modelshard",
        "budget_bytes": budget,
        "forced": forced,
        "note": ("unsharded = replicated structure on every device "
                 "(trips REPRO_DEVICE_MEM_BUDGET, the artificial HBM "
                 "stand-in on forced host devices); model-sharded = pin "
                 "tables row-sharded over the mesh model axis with "
                 "psum'd segment-sums (DESIGN.md §15).  Rows only exist "
                 "because the gates passed: the unsharded arm raised "
                 "DeviceBudgetExceeded, the sharded arm's cut was "
                 "recomputed from its partition and matched, and the "
                 "moderate-size parity gate held bit-identity mesh vs "
                 "off.  Forced host devices share one CPU's FLOPs, so "
                 "wall_s tracks dispatch cost, not a speedup "
                 "(docs/reference.md caveats)."),
    }
    if local is not None:
        record["local"] = local
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path} (sharded wall "
              f"{res['rows'][1]['wall_s']}s)", file=out)
    return record


if __name__ == "__main__":
    json_dir = None
    if "--json-dir" in sys.argv:
        i = sys.argv.index("--json-dir") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("--json-dir requires a directory argument")
        json_dir = sys.argv[i]
        os.makedirs(json_dir, exist_ok=True)
    jp = ("BENCH_modelshard.json" if json_dir is None
          else os.path.join(json_dir, "BENCH_modelshard.json"))
    bench_modelshard(smoke="--smoke" in sys.argv, json_path=jp)
