"""Paper Fig. 6: scalability to large k (k = 4, 10, 16, 32) — normalized
cut vs the multilevel baseline; the paper's claim is that IMPart's margin
holds/grows with k.

Also home of three engine benchmarks tracked PR over PR:

* ``bench_population`` — batched-vs-looped uncoarsening+refinement at
  alpha=7, k=64 (``BENCH_population.json``), exercising the fused
  on-device LP attempt loop, plus a sharded row per population shard
  path (off / chunk / mesh, DESIGN.md §11) recording device count so
  the mesh-vs-chunk ratio is tracked like every other engine pair;
* ``bench_gain`` — the gain-path k-sweep (k = 64, 256, 1024): the old
  [P, k] segment-sum vs the ``kernels.ops`` dispatcher
  (``BENCH_gain.json``);
* ``bench_mutation`` — the population-batched mutation V-cycle vs the
  per-member reference loop (``BENCH_mutation.json``): one shared-
  structure cohort hierarchy either way, batched vs per-member
  dispatches, bit-identical per-member partitions asserted every run.

``--smoke`` runs all three at tiny sizes plus a forced sweep over every
gain path, both coarsening engines (``REPRO_COARSEN_PATH=host|device``),
both mutation paths (``REPRO_MUTATE_PATH=batch|loop``, kernels in
interpret mode) AND all three population shard paths
(``REPRO_POP_SHARD=mesh|chunk|off``, bit-identical per-member results
required), so CI fails on kernel/engine-routing breakage rather than on
perf graphs.  ``--json-dir DIR`` makes the smoke benches write their
records there (uploaded as workflow artifacts by CI).
"""
from __future__ import annotations

import json
import sys
import time
from functools import partial

import numpy as np

from repro.data.hypergraphs import titan_like
from .partition_common import run_methods

METHODS = ("multilevel", "ext_memetic", "impart")


# --------------------------------------------------------------------------
# legacy looped baseline (the seed implementation this PR removed from
# impart.py: per-member host loop + fixed-length FM scan) — vendored here
# so the speedup keeps being measured against the true "before"
# --------------------------------------------------------------------------
def _legacy_fm_pass(hga, part, k, cap, steps):
    import jax
    import jax.numpy as jnp
    from repro.core import metrics
    from repro.core.refine import NEG

    n_pad = hga.n_pad
    valid = (jnp.arange(n_pad) < hga.n) & (hga.vertex_weights > 0)
    phi0 = metrics.pins_in_block(hga, part, k)
    bw0 = metrics.block_weights(hga, part, k)
    cut0 = metrics.cutsize(hga, part, k)

    def step(carry, _):
        part, phi, bw, locked, cur_cut, best_cut, best_part = carry
        gains = metrics.gain_matrix(hga, part, k, phi=phi)
        own = jax.nn.one_hot(part, k, dtype=bool)
        feasible = (bw[None, :] + hga.vertex_weights[:, None]) <= cap + 1e-6
        score = jnp.where(own | ~feasible, NEG, gains)
        score = jnp.where((locked | ~valid)[:, None], NEG, score)
        flat = jnp.argmax(score)
        v = (flat // k).astype(jnp.int32)
        j = (flat % k).astype(jnp.int32)
        g = score.reshape(-1)[flat]
        do = g > NEG / 2
        b = part[v]
        d = jax.ops.segment_sum(
            (hga.pin_vertex == v).astype(jnp.int32), hga.pin_edge,
            num_segments=hga.m_pad)
        delta = (jax.nn.one_hot(j, k, dtype=phi.dtype)
                 - jax.nn.one_hot(b, k, dtype=phi.dtype))
        part = jnp.where(do, part.at[v].set(j), part)
        phi = jnp.where(do, phi + d[:, None] * delta[None, :], phi)
        bw = jnp.where(do, bw + hga.vertex_weights[v] * delta, bw)
        locked = locked.at[v].set(jnp.where(do, True, locked[v]))
        cur_cut = jnp.where(do, cur_cut - g, cur_cut)
        better = do & (cur_cut < best_cut - 1e-9)
        best_cut = jnp.where(better, cur_cut, best_cut)
        best_part = jnp.where(better, part, best_part)
        return (part, phi, bw, locked, cur_cut, best_cut, best_part), None

    locked0 = jnp.zeros(n_pad, bool)
    init = (part, phi0, bw0, locked0, cut0, cut0, part)
    (_, _, _, _, _, best_cut, best_part), _ = jax.lax.scan(
        step, init, None, length=steps)
    return best_part, best_cut


def _get_legacy_fm_pass_jit():
    import jax
    return jax.jit(_legacy_fm_pass, static_argnames=("k", "steps"))


def _legacy_fm_refine(fm_pass_jit, hga, part, k, eps):
    from repro.core import metrics
    from repro.core.refine import pad_part
    cap = metrics.balance_cap(hga.total_weight, k, eps)
    part = pad_part(part, hga.n_pad)
    cut = float(metrics.cutsize_jit(hga, part, k))
    steps = int(min(hga.n_pad, 1024))
    for _ in range(8):
        cand, c = fm_pass_jit(hga, part, k, cap, steps)
        c = float(c)
        if c < cut - 1e-6:
            part, cut = cand, c
        else:
            break
    return np.asarray(part), cut


def _uncoarsen_refine_phase(hier, parts0, k, eps, mode, lp_iters,
                            fm_node_limit, fm_pass_jit=None, shard=None):
    """The phase impart_partition runs between recombination rounds, in
    either engine.  ``looped`` replicates the removed per-member loop;
    ``shard`` forces a population shard path for the batched engine."""
    from repro.core import refine as refine_mod
    parts = parts0.copy()
    cuts = None
    num = len(hier.levels)
    for li in range(num - 1, -1, -1):
        lv = hier.levels[li]
        if li < num - 1:
            parts = parts[:, hier.levels[li + 1].cluster_id]
        hga = lv.hg.arrays()
        if mode == "batched":
            pp, cuts = refine_mod.refine_population(
                hga, parts, k, eps, fm_node_limit=fm_node_limit,
                max_iters=lp_iters, shard=shard)
            parts = pp[:, : lv.hg.n]
        else:
            ps, cs = [], []
            for a in range(parts.shape[0]):
                q, c = refine_mod.lp_refine(hga, parts[a], k, eps,
                                            max_iters=lp_iters)
                if int(hga.n) <= fm_node_limit:
                    q, c = _legacy_fm_refine(fm_pass_jit, hga, q, k, eps)
                ps.append(np.asarray(q)[: lv.hg.n])
                cs.append(c)
            parts = np.stack(ps)
            cuts = np.asarray(cs, np.float64)
    return parts, cuts


def bench_gain(quick: bool = False, out=sys.stdout,
               json_path: str | None = "BENCH_gain.json",
               ks=None, scale: float = 0.1, reps: int = 3):
    """Gain-path k-sweep: old [P, k] segment-sum vs the dispatcher.

    On CPU the dispatcher resolves to the compact sparse assembly for
    k > KERNEL_MAX_K (the Pallas kernels are TPU-path, verified by the
    parity tests); the interpret-mode numbers still measure the real
    O(P * k) -> O(P) work reduction.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import metrics, refine
    from repro.kernels import ops

    hg = titan_like("gsm_switch_like", scale=scale)
    hga = hg.arrays()
    ks = tuple(ks) if ks is not None else ((64, 256) if quick
                                           else (64, 256, 1024))

    def timeit(fn):
        jax.block_until_ready(fn())          # warm-up / compile
        best = float("inf")
        for _ in range(reps):                # best-of: this box is noisy
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    rng = np.random.default_rng(0)
    rows = []
    print("table,design,k,path,segsum_ms,dispatch_ms,speedup,exact",
          file=out)
    for k in ks:
        part = refine.pad_part(rng.integers(0, k, hg.n).astype(np.int32),
                               hga.n_pad)
        path = ops.gain_path(k, incidence=hga.incident is not None)
        t_ref = timeit(lambda: metrics.gain_matrix_jit(
            hga, part, k, assemble="segsum"))
        t_new = timeit(lambda: metrics.gain_matrix_jit(hga, part, k))
        exact = bool(jnp.array_equal(
            metrics.gain_matrix_jit(hga, part, k, assemble="segsum"),
            metrics.gain_matrix_jit(hga, part, k)))
        row = {"k": k, "path": path,
               "segsum_ms": round(t_ref * 1e3, 3),
               "dispatch_ms": round(t_new * 1e3, 3),
               "speedup": round(t_ref / t_new, 3), "exact": exact}
        rows.append(row)
        print(f"gain,gsm_switch_like,{k},{path},{row['segsum_ms']:.1f},"
              f"{row['dispatch_ms']:.1f},{row['speedup']:.2f},{exact}",
              file=out)
    if json_path:
        record = {"bench": "gain_path", "design": "gsm_switch_like",
                  "n": hg.n, "m": hg.m, "pins": hg.num_pins,
                  "backend": jax.default_backend(),
                  "interpret": ops.interpret_mode(), "reps": reps,
                  "sweep": rows}
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path}", file=out)
    return rows


def _smoke_gain_paths(out=sys.stdout):
    """Force every gain path through metrics.gain_matrix on a tiny
    instance and require agreement — kernel routing breakage fails CI
    here, independent of timings."""
    import os
    import jax
    import jax.numpy as jnp

    results = {}
    for path in ("segsum", "compact", "stream"):
        os.environ["REPRO_GAIN_PATH"] = path
        jax.clear_caches()
        from repro.core import metrics, refine
        hg = titan_like("gsm_switch_like", scale=0.01)
        hga = hg.arrays()
        for k in (8, 40):
            part = refine.pad_part(
                np.random.default_rng(0).integers(0, k, hg.n).astype(
                    np.int32), hga.n_pad)
            results.setdefault(k, {})[path] = np.asarray(
                metrics.gain_matrix_jit(hga, part, k))
    os.environ.pop("REPRO_GAIN_PATH", None)
    jax.clear_caches()
    for k, by_path in results.items():
        base = by_path["segsum"]
        for path, got in by_path.items():
            err = float(np.abs(got - base).max())
            print(f"smoke,gain_path,{k},{path},maxerr={err:.1e}", file=out)
            assert err < 1e-4, f"gain path {path} diverged at k={k}: {err}"


def _smoke_coarsen_paths(out=sys.stdout):
    """Force BOTH coarsening engines end-to-end through impart + vcycle
    on a tiny instance and require agreement — mirroring the four-path
    gain smoke: engine-routing breakage fails CI here, not on perf
    graphs.  Tie-breaking differs between engines, so the check is cut
    sanity (balanced, never worse than the V-cycle input, device within
    a loose factor of host on this tiny instance), not bit equality."""
    import os
    import jax
    from repro.core.impart import impart_partition, ImpartConfig
    from repro.core.vcycle import vcycle
    from repro.core import metrics
    from repro.core import refine as refine_mod

    base = titan_like("gsm_switch_like", scale=0.02)
    k, eps = 8, 0.08
    cuts = {}
    prior = os.environ.get("REPRO_COARSEN_PATH")
    try:
        for path in ("host", "device"):
            os.environ["REPRO_COARSEN_PATH"] = path
            jax.clear_caches()
            hg = base.structural_copy()
            res = impart_partition(hg, ImpartConfig(k=k, eps=eps, alpha=2,
                                                    beta=2, seed=3,
                                                    lp_iters=4,
                                                    final_vcycles=0))
            hga = hg.arrays()
            assert bool(metrics.is_balanced(
                hga, refine_mod.pad_part(res.part, hga.n_pad), k, eps))
            rng = np.random.default_rng(0)
            part0 = refine_mod.rebalance(
                hg.vertex_weights, rng.integers(0, k, hg.n).astype(np.int32),
                k, eps, rng)
            c0 = float(metrics.cutsize_jit(
                hga, refine_mod.pad_part(part0, hga.n_pad), k))
            _, cv = vcycle(hg, part0, k, eps, seed=5)
            assert cv <= c0 + 1e-6, f"{path} vcycle regressed: {c0} -> {cv}"
            cuts[path] = res.cut
            print(f"smoke,coarsen_path,{path},impart_cut={res.cut:.0f},"
                  f"vcycle={c0:.0f}->{cv:.0f}", file=out)
    finally:
        if prior is None:
            os.environ.pop("REPRO_COARSEN_PATH", None)
        else:
            os.environ["REPRO_COARSEN_PATH"] = prior
        jax.clear_caches()
    ratio = cuts["device"] / max(cuts["host"], 1e-9)
    print(f"smoke,coarsen_path,ratio,{ratio:.3f},", file=out)
    assert 0.7 <= ratio <= 1.3, f"coarsen engines diverged: {cuts}"


def _smoke_mutate_paths(out=sys.stdout):
    """Force BOTH mutation paths through ``mutate_population`` on a tiny
    instance and require bit-identical per-member partitions and cuts —
    the cohort V-cycle's acceptance bar, enforced in CI."""
    import os
    import numpy as np
    from repro.core import metrics
    from repro.core import refine as refine_mod
    from repro.core.mutate import mutate_population

    hg = titan_like("gsm_switch_like", scale=0.01)
    k, eps = 8, 0.08
    rng = np.random.default_rng(0)
    hga = hg.arrays()
    base = refine_mod.rebalance(
        hg.vertex_weights, rng.integers(0, k, hg.n).astype(np.int32),
        k, eps)
    base, _ = refine_mod.lp_refine(hga, base, k, eps, max_iters=2)
    parts = np.stack([np.asarray(base)[: hg.n]] * 3)
    cuts = [float(metrics.cutsize_jit(
        hga, refine_mod.pad_part(p, hga.n_pad), k)) for p in parts]
    results = {}
    prior = os.environ.get("REPRO_MUTATE_PATH")
    try:
        for path in ("loop", "batch"):
            os.environ["REPRO_MUTATE_PATH"] = path
            results[path] = mutate_population(hg, parts, cuts, k, eps,
                                              seed=1)
            print(f"smoke,mutate_path,{path},"
                  f"cuts={[round(c) for c in results[path][1]]}", file=out)
    finally:
        if prior is None:
            os.environ.pop("REPRO_MUTATE_PATH", None)
        else:
            os.environ["REPRO_MUTATE_PATH"] = prior
    assert np.array_equal(results["batch"][0], results["loop"][0]), \
        "mutation paths diverged (partitions)"
    assert np.array_equal(results["batch"][1], results["loop"][1]), \
        "mutation paths diverged (cuts)"
    print("smoke,mutate_path,parity,bit-identical", file=out)


def _smoke_pop_shard_paths(out=sys.stdout):
    """Force every population shard path (mesh / chunk / off) through
    ``refine_population`` on a tiny instance and require bit-identical
    per-member partitions and cuts — the DESIGN.md §11 parity bar,
    enforced in CI at whatever device count the lane exposes (the
    multidevice CI job runs this on 8 forced host devices)."""
    import jax
    from repro.core import popshard
    from repro.core import refine as refine_mod

    hg = titan_like("gsm_switch_like", scale=0.01)
    k, eps, alpha = 8, 0.08, 3
    rng = np.random.default_rng(0)
    hga = hg.arrays()
    parts = [refine_mod.rebalance(
        hg.vertex_weights, rng.integers(0, k, hg.n).astype(np.int32),
        k, eps) for _ in range(alpha)]
    results = {}
    for path in popshard.POP_SHARD_PATHS:
        results[path] = refine_mod.refine_population(
            hga, [p.copy() for p in parts], k, eps, max_iters=4,
            shard=path)
        print(f"smoke,pop_shard,{path},devices={len(jax.local_devices())},"
              f"cuts={[round(float(c)) for c in results[path][1]]}",
              file=out)
    for path in ("mesh", "chunk"):
        assert np.array_equal(results[path][0], results["off"][0]), \
            f"pop shard path {path} diverged (partitions)"
        assert np.array_equal(results[path][1], results["off"][1]), \
            f"pop shard path {path} diverged (cuts)"
    print("smoke,pop_shard,parity,bit-identical", file=out)


def smoke(out=sys.stdout, json_dir: str | None = None):
    """CI entry: tiny-size routing + engine checks.  With ``json_dir``
    the bench records are written there (tiny smoke-scale numbers, the
    workflow-artifact perf trail; the committed repo-root JSONs stay the
    full-scale measurements)."""
    import os
    jp = (lambda name: None) if json_dir is None else (
        lambda name: os.path.join(json_dir, name))
    if json_dir is not None:
        os.makedirs(json_dir, exist_ok=True)
    _smoke_gain_paths(out=out)
    _smoke_coarsen_paths(out=out)
    _smoke_mutate_paths(out=out)
    _smoke_pop_shard_paths(out=out)
    bench_gain(json_path=jp("BENCH_gain.json"), ks=(8, 40), scale=0.02,
               reps=1, out=out)
    bench_population(quick=True, smoke=True,
                     json_path=jp("BENCH_population.json"), out=out)
    bench_mutation(quick=True, smoke=True,
                   json_path=jp("BENCH_mutation.json"), out=out)
    print("# smoke OK", file=out)


def bench_population(quick: bool = False, out=sys.stdout,
                     json_path: str | None = "BENCH_population.json",
                     smoke: bool = False):
    """Batched population engine vs the removed per-member loop.

    alpha=7 / k=64 on a scaled gsm_switch-like netlist; both engines run
    the identical uncoarsening+refinement phase (same config, bit-equal
    per-member cuts) — only the dispatch strategy differs.
    """
    from repro.core.coarsen import coarsen
    from repro.core.initial_partition import initial_partition

    design = "gsm_switch_like"
    if smoke:   # CI routing check: tiny instance, same code path
        alpha, k, eps = 3, 16, 0.08
        lp_iters, fm_node_limit = 4, 4096
        hg = titan_like(design, scale=0.01)
    else:
        alpha, k, eps = 7, 64, 0.08
        lp_iters, fm_node_limit = 16, 4096
        hg = titan_like(design, scale=0.02)
    hier = coarsen(hg, k, seed=11, contraction_limit_factor=4)

    parts0 = np.stack([
        np.asarray(initial_partition(hier.coarsest, k, eps, seed=101 + i,
                                     tries_per_strategy=1)[0],
                   np.int32)[: hier.coarsest.n]
        for i in range(alpha)])

    fm_pass_jit = _get_legacy_fm_pass_jit()
    phase = partial(_uncoarsen_refine_phase, hier, parts0, k, eps,
                    lp_iters=lp_iters, fm_node_limit=fm_node_limit,
                    fm_pass_jit=fm_pass_jit)
    reps = 1 if quick else 2

    def timeit(run):
        run()  # warm-up / compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            parts, cuts = run()
            times.append(time.perf_counter() - t0)
        return {"wall_s": min(times), "cuts": cuts}

    # base comparison on the single-device engine (shard="off") so the
    # looped-vs-batched speedup stays comparable PR over PR regardless
    # of how many devices the box exposes
    results = {mode: timeit(partial(phase, mode=mode, shard="off"))
               for mode in ("looped", "batched")}

    looped, batched = results["looped"], results["batched"]
    cuts_equal = bool(np.array_equal(looped["cuts"], batched["cuts"]))
    if not cuts_equal:
        raise RuntimeError(
            "batched engine diverged from the looped baseline: "
            f"looped={looped['cuts']} batched={batched['cuts']} — the "
            "speedup below would compare non-equivalent work")
    speedup = looped["wall_s"] / batched["wall_s"]
    print("table,design,alpha,k,engine,wall_s,speedup,cuts_equal", file=out)
    for mode in ("looped", "batched"):
        print(f"population,{design},{alpha},{k},{mode},"
              f"{results[mode]['wall_s']:.2f},"
              f"{speedup if mode == 'batched' else 1.0:.2f},"
              f"{cuts_equal}", file=out)

    # the sharded rows: the same batched phase over each population
    # shard path (DESIGN.md §11), so the mesh-vs-chunk ratio is tracked
    # like every other engine pair; device count rides in the JSON
    import jax
    from repro.core import popshard
    ndev = len(jax.local_devices())
    shard_wall = {"off": batched["wall_s"]}
    for spath in ("chunk", "mesh"):
        r = timeit(partial(phase, mode="batched", shard=spath))
        if not np.array_equal(r["cuts"], batched["cuts"]):
            raise RuntimeError(
                f"shard path {spath!r} diverged from the single-device "
                f"engine: off={batched['cuts']} {spath}={r['cuts']}")
        shard_wall[spath] = r["wall_s"]
    print("table,design,alpha,k,shard_path,devices,wall_s,cuts_equal",
          file=out)
    for spath, wall in shard_wall.items():
        print(f"population_shard,{design},{alpha},{k},{spath},{ndev},"
              f"{wall:.2f},True", file=out)

    record = {
        "bench": "population_refinement",
        "design": design, "n": hg.n, "m": hg.m,
        "levels": hier.sizes(),
        "alpha": alpha, "k": k, "eps": eps,
        "lp_iters": lp_iters, "fm_node_limit": fm_node_limit,
        "looped_wall_s": round(looped["wall_s"], 3),
        "batched_wall_s": round(batched["wall_s"], 3),
        "speedup": round(speedup, 3),
        "cuts_equal": cuts_equal,
        "per_member_cuts": [float(c) for c in batched["cuts"]],
        "shard": {
            "devices": ndev,
            "auto_path": popshard.pop_shard_path(),
            "wall_s": {p: round(w, 3) for p, w in shard_wall.items()},
            "cuts_equal": True,
            "note": ("same batched phase under each REPRO_POP_SHARD "
                     "path, bit-equal per-member cuts asserted; on a "
                     "single-device host mesh/chunk degenerate to off "
                     "plus dispatch overhead — the mesh win needs real "
                     "devices (TPU) or forced host devices"),
        },
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path} (speedup {speedup:.2f}x, "
              f"cuts_equal={cuts_equal})", file=out)
    return record


def bench_mutation(quick: bool = False, out=sys.stdout,
                   json_path: str | None = "BENCH_mutation.json",
                   smoke: bool = False):
    """Population-batched mutation V-cycle vs the per-member loop.

    A flagged cohort (identical warm starts, mutation-style per-member
    reweights w'_e = w_e * (1 + 0.1 * C(e)) from the other members' cut
    indicators) runs ``vcycle_population`` both ways: ``batch`` — every
    per-member stage one cohort dispatch — and ``loop`` — the identical
    pipeline member-at-a-time.  Both build the same shared-structure
    hierarchy, so per-member partitions must match bit-for-bit (asserted
    every run; the speedup never compares non-equivalent work).

    A third timed row, ``legacy``, replays the pre-cohort mutation path
    (one scalar ``vcycle`` per member, each building its OWN per-member
    hierarchy on its reweighted copy) so the JSON also records the
    speedup over the true "before" — its cuts come from different
    hierarchies and are NOT expected to match, so it never enters the
    parity assertion.
    """
    import numpy as np
    from repro.core import metrics
    from repro.core import refine as refine_mod
    from repro.core.vcycle import vcycle, vcycle_population

    design = "gsm_switch_like"
    if smoke:
        alpha, k, eps = 3, 16, 0.08
        hg = titan_like(design, scale=0.01)
    else:
        alpha, k, eps = 5, 64, 0.08
        hg = titan_like(design, scale=0.02)
    rng = np.random.default_rng(0)
    hga = hg.arrays()
    base = refine_mod.rebalance(
        hg.vertex_weights, rng.integers(0, k, hg.n).astype(np.int32),
        k, eps)
    base, _ = refine_mod.lp_refine(hga, base, k, eps, max_iters=4)
    parts = np.stack([np.asarray(base)[: hg.n]] * alpha)
    # mutation-style reweights: member j pays for edges the others cut
    lam = np.asarray(metrics.connectivity_population(
        hga, refine_mod.pad_parts(parts, hga.n_pad), k))[:, : hg.m]
    cut_ind = (lam > 1).astype(np.float64)
    w_pop = np.stack([
        hg.edge_weights * (1.0 + 0.1 * np.delete(cut_ind, j, 0).sum(0))
        for j in range(alpha)]).astype(np.float32)

    def legacy():  # the pre-cohort path: one hierarchy per member
        outs, cuts = [], []
        for a in range(alpha):
            rw = hg.with_edge_weights(w_pop[a])
            p, c = vcycle(rw, parts[a], k, eps, seed=3 * 7919 + a)
            outs.append(np.asarray(p)[: hg.n])
            cuts.append(c)
        return np.stack(outs), np.asarray(cuts)

    reps = 1 if (quick or smoke) else 2
    results = {}
    for mode in ("legacy", "loop", "batch"):
        runner = legacy if mode == "legacy" else (
            lambda: vcycle_population(hg, parts, w_pop, k, eps, seed=3,
                                      path=mode))
        runner()  # warm-up / compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            pout, cout = runner()
            times.append(time.perf_counter() - t0)
        results[mode] = {"wall_s": min(times), "parts": pout, "cuts": cout}

    looped, batched = results["loop"], results["batch"]
    parts_equal = bool(
        np.array_equal(looped["parts"], batched["parts"])
        and np.array_equal(looped["cuts"], batched["cuts"]))
    if not parts_equal:
        raise RuntimeError(
            "batched mutation diverged from the per-member loop: "
            f"loop={looped['cuts']} batch={batched['cuts']} — the "
            "speedup below would compare non-equivalent work")
    speedup = looped["wall_s"] / batched["wall_s"]
    speedup_legacy = results["legacy"]["wall_s"] / batched["wall_s"]
    print("table,design,alpha,k,engine,wall_s,speedup,parts_equal",
          file=out)
    for mode, sp in (("legacy", 1.0), ("loop", 1.0), ("batch", speedup)):
        print(f"mutation,{design},{alpha},{k},{mode},"
              f"{results[mode]['wall_s']:.2f},{sp:.2f},"
              f"{parts_equal if mode != 'legacy' else 'n/a'}", file=out)

    if json_path:
        import jax
        from repro.kernels import ops
        record = {
            "bench": "mutation_vcycle",
            "design": design, "n": hg.n, "m": hg.m, "pins": hg.num_pins,
            "alpha_flagged": alpha, "k": k, "eps": eps,
            "backend": jax.default_backend(),
            "interpret": ops.interpret_mode(),
            "legacy_per_member_wall_s": round(results["legacy"]["wall_s"],
                                              3),
            "looped_wall_s": round(looped["wall_s"], 3),
            "batched_wall_s": round(batched["wall_s"], 3),
            "speedup": round(speedup, 3),
            "speedup_vs_legacy": round(speedup_legacy, 3),
            "parts_equal": parts_equal,
            "per_member_cuts": [float(c) for c in batched["cuts"]],
            "note": ("legacy = the pre-cohort path, one scalar vcycle + "
                     "per-member hierarchy per flagged member (its cuts "
                     "come from different hierarchies and are excluded "
                     "from the parity assertion); loop/batch share one "
                     "cohort hierarchy and must match bit-for-bit"),
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"# wrote {json_path} (speedup {speedup:.2f}x, "
              f"parts_equal={parts_equal})", file=out)
    return results


def run(quick: bool = False, out=sys.stdout):
    hg = titan_like("gsm_switch_like", scale=0.04 if quick else 0.06)
    ks = [4, 10] if quick else [4, 10, 16, 32]
    print("table,design,k,eps,method,cut,normalized,wall_s", file=out)
    for k in ks:
        eps = k * 0.02  # paper: imbalance = 2% of |V| => eps = k * p
        res = run_methods(hg, k, eps, seed=11, alpha=3 if quick else 5,
                          beta=3 if quick else 5, methods=METHODS)
        ref = res["multilevel"]["cut"]
        for m in METHODS:
            print(f"largek,gsm_switch_like,{k},{eps},{m},"
                  f"{res[m]['cut']:.0f},{res[m]['cut'] / ref:.4f},"
                  f"{res[m]['wall_s']:.1f}", file=out)
    bench_population(quick=quick, out=out)
    bench_gain(quick=quick, out=out)
    bench_mutation(quick=quick, out=out)
    return None


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        json_dir = None
        if "--json-dir" in sys.argv:
            i = sys.argv.index("--json-dir") + 1
            if i >= len(sys.argv) or sys.argv[i].startswith("--"):
                sys.exit("--json-dir requires a directory argument")
            json_dir = sys.argv[i]
        smoke(json_dir=json_dir)
    else:
        run(quick="--quick" in sys.argv)
