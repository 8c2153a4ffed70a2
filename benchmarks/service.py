"""Continuous-batching partition service benchmark (``BENCH_service.json``).

Replays a crc32-seeded ``request_stream`` workload through
``serve.partition_service.PartitionService`` at two or more offered-load
points and records per-request latency (p50 / p99) and completed
throughput, on the current device topology AND on the opposite one (a
subprocess with ``--xla_force_host_platform_device_count`` forced, the
``test_pop_shard.py`` idiom), so the JSON always carries a
single-device and a multi-device row set.

Every run first solves each request ALONE through ``solve_solo`` — that
both warms the compile caches and pins the parity reference: after every
measured load point each request's part and cut must be bit-identical to
its solo answer (``cuts_equal``), so the latency numbers never come from
non-equivalent work.  Batching is a scheduling choice, not an answer
change (DESIGN.md §12).

``--smoke`` runs tiny sizes for CI; ``--json-dir DIR`` redirects the
record there (the workflow-artifact perf trail; the committed repo-root
JSON stays the full-scale measurement).

``--faults`` instead runs the robustness soak (``BENCH_robustness.json``,
DESIGN.md §13): one faulted service run per fault kind — device loss,
mid-tick crash, state corruption, straggler — against an unfaulted
control, recording recovery wall-clock, the terminal-outcome histogram,
and ``cuts_equal`` for every request the fault did not touch.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pct(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def measure_rows(nreq: int, loads, scale: float, slots: int = 4,
                 coalesce_ms: float = 0.0, shard=None, out=sys.stdout):
    """Run the workload at each offered load (requests/s) and return
    ``{"devices", "backend", "shard_path", "rows"}``.  Raises if any
    request's batched answer differs from its solo answer."""
    import jax
    from repro.core import popshard
    from repro.data.hypergraphs import request_stream
    from repro.serve.partition_service import (PartitionRequest,
                                               PartitionService)

    reqs = request_stream(nreq, tag="bench", scale=scale)

    def make(r):
        return PartitionRequest(name=r["name"], hg=r["hg"], k=r["k"],
                                eps=r["eps"])

    # parity reference + compile warm-up: every request solo, then the
    # whole stream through one service (compiles the grouped shapes)
    svc = PartitionService(slots=slots, coalesce_ms=coalesce_ms,
                          shard=shard)
    solo = {r["name"]: svc.solve_solo(make(r)) for r in reqs}
    for r in reqs:
        svc.submit(make(r))
    svc.drain()

    def check(service):
        for r in reqs:
            got = service.results[r["name"]]
            ref_part, ref_cut = solo[r["name"]]
            if got.cut != ref_cut or not np.array_equal(got.part, ref_part):
                raise RuntimeError(
                    f"service answer for {r['name']} diverged from solo: "
                    f"cut {got.cut} vs {ref_cut} — the latency rows would "
                    "measure non-equivalent work")

    check(svc)
    rows = []
    for load in loads:
        service = PartitionService(slots=slots, coalesce_ms=coalesce_ms,
                                   shard=shard)
        gap = 1.0 / float(load)
        t0 = time.perf_counter()
        nxt = 0
        while nxt < nreq or service.busy:
            now = time.perf_counter() - t0
            while nxt < nreq and now >= nxt * gap:
                service.submit(make(reqs[nxt]))
                nxt += 1
            if service.busy:
                service.step()
            else:
                time.sleep(min(gap / 8, 0.002))
        makespan = time.perf_counter() - t0
        check(service)
        lats = [res.latency_s for res in service.results.values()]
        row = {"offered_load_rps": float(load), "completed": len(lats),
               "throughput_rps": round(len(lats) / makespan, 3),
               "p50_ms": round(_pct(lats, 50) * 1e3, 2),
               "p99_ms": round(_pct(lats, 99) * 1e3, 2),
               "makespan_s": round(makespan, 3), "cuts_equal": True}
        rows.append(row)
        print(f"service,devices={len(jax.local_devices())},"
              f"offered={load},thr={row['throughput_rps']},"
              f"p50={row['p50_ms']}ms,p99={row['p99_ms']}ms,"
              f"cuts_equal=True", file=out)
    return {"devices": len(jax.local_devices()),
            "backend": jax.default_backend(),
            "shard_path": popshard.resolve(shard), "rows": rows}


def _rows_subprocess(ndev: int, nreq: int, loads, scale: float,
                     slots: int, out=sys.stdout):
    """The same measurement in a fresh process with ``ndev`` forced host
    devices (progress on stderr, JSON record on stdout)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), _REPO])
    code = (
        "import json, sys\n"
        "from benchmarks.service import measure_rows\n"
        f"r = measure_rows({nreq}, {tuple(loads)!r}, {scale!r}, "
        f"slots={slots}, out=sys.stderr)\n"
        "print(json.dumps(r))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=_REPO, capture_output=True, text=True,
                          timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"forced-{ndev}-device service run failed:\n{proc.stderr}")
    print(f"# forced {ndev}-device subprocess done", file=out)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_service(smoke: bool = False, out=sys.stdout,
                  json_path: str | None = "BENCH_service.json"):
    """Emit BENCH_service.json: p50/p99 latency + throughput at >= 2
    offered loads, single-device and multi-device, parity asserted."""
    import jax
    if smoke:
        nreq, loads, scale, slots = 6, (2.0, 8.0), 0.35, 3
    else:
        nreq, loads, scale, slots = 12, (1.0, 4.0), 1.0, 4
    ndev = len(jax.local_devices())
    local = measure_rows(nreq, loads, scale, slots=slots, out=out)
    forced = None
    if jax.default_backend() == "cpu":
        other = 8 if ndev == 1 else 1
        forced = _rows_subprocess(other, nreq, loads, scale, slots, out=out)
    else:
        # this process holds the device: a child could not reach it, and
        # forced host-CPU rows would pass for device rows
        print(f"# {jax.default_backend()} backend: forced-host-device "
              "rows skipped", file=out)
    single = local if local["devices"] == 1 else forced
    multi = forced if single is local else local
    record = {
        "bench": "partition_service",
        "nreq": nreq, "scale": scale, "slots": slots,
        "alpha": 4, "lp_iters": 8,
        "offered_loads_rps": list(loads),
        "cuts_equal": True,
        "single_device": single,
        "multi_device": multi,
        "note": ("each request's part+cut asserted bit-identical to "
                 "solve_solo at every load point; one of the two row "
                 "sets runs in a subprocess with "
                 "--xla_force_host_platform_device_count forced — on a "
                 "CPU box, forced host devices OVERSUBSCRIBE the cores "
                 "(8 devices on 2 cores here), so the multi-device rows "
                 "track dispatch correctness and parity, not a speedup; "
                 "the mesh win needs real devices (see "
                 "docs/reference.md, CPU-vs-TPU caveats)"),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        devices = ", ".join(f"{label}={rows['devices']}d"
                            for label, rows in (("single", single),
                                                ("multi", multi))
                            if rows is not None)
        print(f"# wrote {json_path} ({devices}, cuts_equal=True)",
              file=out)
    return record


def _fault_stream(nreq: int):
    """Deeper ladders than ``request_stream`` (~8 levels at
    contraction_limit_factor=16) so scheduled faults land mid-flight."""
    from repro.data.hypergraphs import _modular_netlist
    out = []
    for i in range(nreq):
        hg = _modular_netlist(360 + 40 * i, 460 + 50 * i, seed=50 + i,
                              n_modules=5, p_local=0.8, fanout_tail=1.5)
        out.append({"name": f"fault-bench-{i}", "hg": hg, "k": 3,
                    "eps": 0.08})
    return out


def bench_service_faults(smoke: bool = False, out=sys.stdout,
                         json_path: str | None = "BENCH_robustness.json"):
    """Emit BENCH_robustness.json: per-fault-kind soak runs with
    recovery time, terminal-outcome counts, and solo parity for every
    unfaulted request (DESIGN.md §13)."""
    import jax
    from repro.serve import faults
    from repro.serve.partition_service import (PartitionRequest,
                                               PartitionService)

    nreq = 4 if smoke else 6
    reqs = _fault_stream(nreq)

    def make(r, seed):
        return PartitionRequest(name=r["name"], hg=r["hg"], k=r["k"],
                                eps=r["eps"], seed=seed)

    def svc_for(plan=None, **kw):
        return PartitionService(slots=4, alpha=2, lp_iters=4,
                                contraction_limit_factor=16,
                                ckpt_every=1, fault_plan=plan, **kw)

    # parity reference (also warms the compile caches)
    ref = svc_for()
    solo = {r["name"]: ref.solve_solo(make(r, i))
            for i, r in enumerate(reqs)}

    plans = {
        "none": None,
        "straggler": "2:straggler:delay_ms=60",
        "crash": "2:crash",
        "corrupt": "3:corrupt:slot=0,mode=block_range",
        "device_loss": "3:device_loss:survivors=2",
        "chaos": ("2:straggler:delay_ms=40;3:device_loss:survivors=2;"
                  "4:corrupt:slot=0,mode=block_range;5:crash"),
    }
    runs = []
    for name, spec in plans.items():
        plan = faults.FaultPlan.parse(spec) if spec else None
        svc = svc_for(plan=plan)
        t0 = time.perf_counter()
        for i, r in enumerate(reqs):
            svc.submit(make(r, i))
        svc.drain()
        makespan = time.perf_counter() - t0
        faulted = {e.get("request") for e in svc.events
                   if e["kind"] in ("corrupt_injected", "quarantine")}
        cuts_equal = True
        for i, r in enumerate(reqs):
            got = svc.results[r["name"]]
            sp, sc = solo[r["name"]]
            if got.part is None or got.cut != sc or \
                    not np.array_equal(got.part, sp):
                if got.status == "ok":
                    raise RuntimeError(
                        f"unfaulted request {r['name']} diverged from "
                        f"solo under plan {name!r}")
                cuts_equal = False
        recovery = [e["recovery_s"] for e in svc.events
                    if e["kind"] == "device_loss"]
        row = {"plan": name, "spec": spec,
               "outcomes": svc.outcome_counts(),
               "cuts_equal_all": cuts_equal,
               "faulted_requests": sorted(x for x in faulted if x),
               "events": sorted({e["kind"] for e in svc.events}),
               "makespan_s": round(makespan, 3),
               "recovery_s": [round(x, 4) for x in recovery]}
        runs.append(row)
        print(f"faults,plan={name},outcomes={row['outcomes']},"
              f"cuts_equal_all={cuts_equal},"
              f"makespan={row['makespan_s']}s", file=out)
        from repro.runtime.elastic import restore_device_pool
        restore_device_pool()

    base = next(r for r in runs if r["plan"] == "none")
    record = {
        "bench": "partition_service_faults",
        "nreq": nreq, "slots": 4, "alpha": 2, "lp_iters": 4,
        "devices": len(jax.local_devices()),
        "backend": jax.default_backend(),
        "baseline_makespan_s": base["makespan_s"],
        "runs": runs,
        "note": ("one soak run per fault plan against the same request "
                 "stream; every request a plan did not fault is asserted "
                 "bit-identical to solve_solo (a divergence raises); "
                 "snapshot-resumed and same-seed-restarted requests are "
                 "deterministic, so cuts_equal_all stays true unless a "
                 "retry had to seed-bump (see DESIGN.md §13); recovery_s "
                 "is the device-loss handler wall-clock (pool shrink + "
                 "snapshot restore for every in-flight slot)"),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path}", file=out)
    return record


if __name__ == "__main__":
    json_dir = None
    if "--json-dir" in sys.argv:
        i = sys.argv.index("--json-dir") + 1
        if i >= len(sys.argv) or sys.argv[i].startswith("--"):
            sys.exit("--json-dir requires a directory argument")
        json_dir = sys.argv[i]
        os.makedirs(json_dir, exist_ok=True)
    if "--faults" in sys.argv:
        jp = ("BENCH_robustness.json" if json_dir is None
              else os.path.join(json_dir, "BENCH_robustness.json"))
        bench_service_faults(smoke="--smoke" in sys.argv, json_path=jp)
    else:
        jp = ("BENCH_service.json" if json_dir is None
              else os.path.join(json_dir, "BENCH_service.json"))
        bench_service(smoke="--smoke" in sys.argv, json_path=jp)
