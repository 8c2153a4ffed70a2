#!/usr/bin/env python3
"""Chip smoke: drive the partitioner's main path once on a TPU.

    python3 chip_smoke.py              # one chip: a solve and a service
    python3 chip_smoke.py --chips 4    # four chips: mesh-vs-off parity only

Everything runs in this one process: the chip belongs to the process
that touched JAX first.  With no TPU visible the script fails before any
work and prints no result.  Phases:

* **solve** — ``impart_partition`` on ``ispd_like("ibm01_like")`` at the
  published ISPD98 ibm01 size (12,752 cells, 14,111 nets), k=64,
  eps=0.08, alpha=7, beta=7, seed 0, auto routes; the cut is recomputed
  in numpy from the pin list and the balance and block ids are checked.
* **service** — a ``PartitionService`` over 6 ``request_stream``
  requests, drained; every result must be ``ok`` and bit-equal to
  ``solve_solo`` in this process.
* **--chips 4** — an ``impart_partition`` solve with ``pop_shard="mesh"``
  against ``pop_shard="off"``, and the service with its instance axis on
  the mesh against an unsharded ``solve_solo``: parts and cuts
  bit-equal.  The population mesh and the placement cache must span
  every device.  ``MESH_SOLVE`` keeps the shapes that pick the routes
  of the one-chip solve — k=64 (the compact gain assembly) and the
  paper's alpha=7, which does not divide the 4-way population axis —
  and cuts only the scale, to a tenth of ibm01 (one level), because
  four chips cost four times as much per second; the service serves 4
  requests at 0.3 of the stream's sizes.

The last line of standard output is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the measurements go on the ``report`` line before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SOLVE = dict(design="ibm01_like", scale=1.0, k=64, eps=0.08, alpha=7,
             beta=7, seed=0)
MESH_SOLVE = dict(SOLVE, scale=0.1)
N_REQUESTS = 6
N_MESH_REQUESTS = 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def numpy_cut(hg, part) -> float:
    """Cut-net weight recomputed from the pin list alone."""
    import numpy as np
    blk = np.asarray(part)[hg.pins]
    starts = hg.edge_offsets[:-1]
    cut = np.minimum.reduceat(blk, starts) != np.maximum.reduceat(blk, starts)
    return float(hg.edge_weights[cut].sum())


def check_partition(hg, part, cut: float, k: int, eps: float) -> dict:
    """The correctness gate of one answer: ids in range, the
    (1+eps)*ceil(W/k) balance cap held, and the reported cut equal to the
    numpy recomputation.  Raises on any violation."""
    import numpy as np
    part = np.asarray(part)
    if part.shape != (hg.n,):
        raise AssertionError(f"part shape {part.shape} != ({hg.n},)")
    if part.min() < 0 or part.max() >= k:
        raise AssertionError(f"block ids outside [0, {k})")
    bw = np.bincount(part, weights=hg.vertex_weights, minlength=k)
    cap = (1.0 + eps) * np.ceil(hg.vertex_weights.sum() / k)
    if bw.max() > cap + 1e-4:
        raise AssertionError(f"unbalanced: max block {bw.max()} > cap {cap}")
    ref = numpy_cut(hg, part)
    if ref != cut:
        raise AssertionError(f"reported cut {cut} != recomputed {ref}")
    return {"cut": cut, "max_block": float(bw.max()), "cap": float(cap)}


def routes(hg, k: int) -> dict:
    """The engines the auto routing picks for ``hg`` at ``k`` (finest
    level): gain assembly, device-coarsener rating, coarsening."""
    from repro.core import dcoarsen
    from repro.kernels import ops
    hga = hg.arrays()
    return {"gain": ops.gain_path(k, incidence=hga.incident is not None),
            "rating": ops.rating_path(dcoarsen.MAX_STRIDE * hga.p_pad),
            "coarsen": dcoarsen.coarsen_path()}


def solve(hg, pop_shard=None, **cfg):
    from repro.core import ImpartConfig, impart_partition
    t0 = time.perf_counter()
    res = impart_partition(hg, ImpartConfig(pop_shard=pop_shard, **cfg))
    return res, time.perf_counter() - t0


def phase_solve(hg, k, eps, alpha, beta, seed) -> dict:
    cfg = dict(k=k, eps=eps, alpha=alpha, beta=beta, seed=seed)
    log(f"solve: n={hg.n} m={hg.m} pins={hg.num_pins} {cfg} "
        f"routes={routes(hg, k)}")
    res, wall = solve(hg, **cfg)
    gate = check_partition(hg, res.part, res.cut, k, eps)
    log(f"solve: wall={wall:.3f}s (compiles included) cut={res.cut} "
        f"levels={res.levels} degraded={res.degraded}")
    return {"n": hg.n, "m": hg.m, "k": k, "cut": res.cut,
            "levels": res.levels, "wall_s": wall,
            "max_block": gate["max_block"], "cap": gate["cap"]}


def requests(count: int, scale: float):
    from repro.data.hypergraphs import request_stream
    from repro.serve import PartitionRequest
    return [PartitionRequest(name=r["name"], hg=r["hg"], k=r["k"],
                             eps=r["eps"], seed=i)
            for i, r in enumerate(request_stream(count, tag="chip_smoke",
                                                 scale=scale))]


def phase_service(reqs, shard=None, solo_shard=None) -> dict:
    """Drain ``reqs`` through a service; every result must be ``ok`` and
    bit-equal to ``solve_solo`` of a service routed by ``solo_shard``."""
    import numpy as np
    from repro.serve import PartitionService
    svc = PartitionService(slots=3, shard=shard)
    solo = PartitionService(slots=1, shard=solo_shard)
    t0 = time.perf_counter()
    for r in reqs:
        svc.submit(r)
    results = {r.name: r for r in svc.drain()}
    wall = time.perf_counter() - t0
    statuses = {}
    for req in reqs:
        got = results[req.name]
        statuses[req.name] = got.status
        if got.status != "ok":
            raise AssertionError(f"{req.name}: status {got.status} "
                                 f"({got.error})")
        part, cut = solo.solve_solo(req)
        if got.cut != cut or not np.array_equal(got.part, part):
            raise AssertionError(f"{req.name}: service answer differs "
                                 "from solve_solo")
        check_partition(req.hg, got.part, got.cut, req.k, req.eps)
        log(f"service {req.name}: n={req.hg.n} k={req.k} "
            f"status={got.status} cut={got.cut} "
            f"latency={got.latency_s:.3f}s == solo")
    log(f"service: {len(reqs)} requests drained in {wall:.3f}s")
    return {"requests": len(reqs), "statuses": statuses, "drain_s": wall}


def phase_mesh(hg, k, eps, alpha, beta, seed) -> dict:
    """Population mesh vs one device: same solve, bit-equal answers, and
    the mesh and the placement cache spanning every local device."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import popshard
    cfg = dict(k=k, eps=eps, alpha=alpha, beta=beta, seed=seed)
    devices = set(jax.local_devices())
    mesh = popshard.pop_mesh()
    if set(mesh.devices.flat) != devices:
        raise AssertionError(f"pop mesh {mesh.shape} does not span the "
                             f"{len(devices)} local devices")
    hga = hg.arrays()
    placed = popshard.device_put_cached(hga, popshard.replicated(mesh))
    rows = popshard.device_put_cached(
        np.zeros((len(devices), hga.n_pad), np.int32),
        NamedSharding(mesh, P("pop")))
    for leaf in jax.tree_util.tree_leaves((placed, rows)):
        if set(leaf.sharding.device_set) != devices:
            raise AssertionError("device_put_cached placed data on "
                                 f"{len(leaf.sharding.device_set)} of "
                                 f"{len(devices)} devices")
    log(f"mesh {dict(mesh.shape)} spans all {len(devices)} devices")
    on, t_on = solve(hg, pop_shard="mesh", **cfg)
    off, t_off = solve(hg, pop_shard="off", **cfg)
    check_partition(hg, on.part, on.cut, k, eps)
    if on.cut != off.cut or not np.array_equal(on.part, off.part):
        raise AssertionError(f"mesh cut {on.cut} / off cut {off.cut}: "
                             "parts differ")
    log(f"solve mesh == off: cut={on.cut} mesh={t_on:.3f}s "
        f"off={t_off:.3f}s")
    return {"mesh": dict(mesh.shape), "cut": on.cut, "mesh_s": t_on,
            "off_s": t_off, "bit_equal": True}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh parity phase")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no repro package under {ROOT}/src",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.env import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"devices: {device}; compile cache: {cache}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU visible (platform "
              f"{device['platform']!r}); refusing to run on it",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {device['count']} "
              "device(s) visible", file=sys.stderr)
        return 1

    from repro.data.hypergraphs import ispd_like
    spec = dict(MESH_SOLVE if args.chips == 4 else SOLVE)
    hg = ispd_like(spec.pop("design"), scale=spec.pop("scale"))
    if args.chips == 4:
        report = {"mesh_solve": phase_mesh(hg, **spec),
                  "mesh_service": phase_service(requests(N_MESH_REQUESTS,
                                                         0.3),
                                                shard="mesh",
                                                solo_shard="off")}
    else:
        report = {"solve": phase_solve(hg, **spec),
                  "service": phase_service(requests(N_REQUESTS, 1.0))}
    log(f"report: {json.dumps(report)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
