"""Partitioner launcher:
``python -m repro.launch.partition --design sparcT1_core_like --k 10``.

Runs IMPart (or a baseline) on a named benchmark netlist and reports
cut / balance / trajectory.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.core import (ImpartConfig, impart_partition,
                        multilevel_best_of, external_memetic, metrics,
                        refine)
from repro.data.hypergraphs import (titan_like, ispd_like, BENCH_TITAN,
                                    BENCH_ISPD)
from repro.env import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--design", default="sparcT1_core_like")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--eps", type=float, default=0.08)
    ap.add_argument("--scale", type=float, default=0.08)
    ap.add_argument("--method", default="impart",
                    choices=["impart", "multilevel", "ext_memetic"])
    ap.add_argument("--alpha", type=int, default=7)
    ap.add_argument("--beta", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    enable_compile_cache()

    if args.design in BENCH_TITAN:
        hg = titan_like(args.design, scale=args.scale)
    elif args.design in BENCH_ISPD:
        hg = ispd_like(args.design, scale=args.scale)
    else:
        raise SystemExit(f"unknown design {args.design}; options: "
                         f"{sorted(BENCH_TITAN) + sorted(BENCH_ISPD)}")
    print(f"[partition] {args.design}: n={hg.n} m={hg.m} pins={hg.num_pins}")

    if args.method == "impart":
        res = impart_partition(hg, ImpartConfig(
            k=args.k, eps=args.eps, alpha=args.alpha, beta=args.beta,
            seed=args.seed))
        part, cut, wall = res.part, res.cut, res.wall_s
        events = [t[2] for t in res.trace]
        print(f"[partition] events: "
              f"{sum(e.startswith('recombine') for e in events)} recomb, "
              f"{sum(e.startswith('mutate') for e in events)} mutations, "
              f"levels={res.levels}")
    elif args.method == "multilevel":
        r = multilevel_best_of(hg, args.k, args.eps, seed=args.seed,
                               repetitions=args.alpha)
        part, cut, wall = r.part, r.cut, r.wall_s
    else:
        r = external_memetic(hg, args.k, args.eps, seed=args.seed,
                             population=args.alpha,
                             generations=args.beta)
        part, cut, wall = r.part, r.cut, r.wall_s

    hga = hg.arrays()
    padded = refine.pad_part(part, hga.n_pad)
    bal = bool(metrics.is_balanced(hga, padded, args.k, args.eps))
    imb = float(metrics.imbalance(hga, padded, args.k))
    print(f"[partition] {args.method}: cut={cut:.0f} balanced={bal} "
          f"imbalance={imb:.3f} wall={wall:.1f}s")
    if args.out:
        np.save(args.out, part)
        print(f"[partition] assignment -> {args.out}")


if __name__ == "__main__":
    main()
