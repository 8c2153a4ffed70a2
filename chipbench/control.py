#!/usr/bin/env python3
"""The readings that the correctness limits are set from.

    python3 chipbench/control.py --workload <cell> --seeds 401,402,403 \\
        [--system program|degraded|reference]

For each seed, in this one process: the cell's requests made from the
seed, each sent once through the closed loop at the cell's own load
(the harness's own loop and check), and the checks printed as one JSON
line.  The last line gives, per check, the largest and the smallest
reading.

* ``program`` -- the program as the configuration states it: the lower
  readings.
* ``degraded`` -- the control: the program's own path with refinement
  switched off (the configuration's ``control.service``).  It breaks
  the refinement guarantee (``fm_gain``) and nothing else.
* ``reference`` -- the plain reference with its refinement taken out in
  the program's place: a random assignment that keeps the cap
  (``reference.random_partition``), with its true cut.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import reference  # noqa: E402
from chipbench import run as R  # noqa: E402
from chipbench.systems import Answer, ServiceSystem  # noqa: E402


class ControlSystem:
    """The reference without refinement, answering every queued request
    at each step with a random assignment under the cap."""

    span_plan = None
    step_span = "tick"

    def __init__(self, config, seed: int = 0):
        self.seed = seed
        self.queue = []

    @property
    def busy(self) -> bool:
        return bool(self.queue)

    def submit(self, rid: str, req) -> None:
        self.queue.append((rid, req.inst))

    def step(self):
        out = []
        for rid, inst in self.queue:
            part = reference.random_partition(inst, self.seed)
            cut = reference.numpy_cut(inst.pins, inst.offsets,
                                      inst.edge_weights, part)
            out.append(Answer(rid, part, cut, "ok", time.perf_counter()))
        self.queue = []
        return out


def degraded(config):
    """The program with the configuration's ``control.service``."""
    return ServiceSystem(config, service=config["control"]["service"])


FACTORIES = {"program": None, "degraded": degraded,
             "reference": ControlSystem}


def readings(cell_files, seed: int, system: str):
    """The checks of one seed's requests, each sent once."""
    bench, cell, config, mix = cell_files
    h = R.Harness(cell, config, mix, seed, 0.0, False, FACTORIES[system])
    h._drive(math.inf, sends=len(h.requests))
    sent, _ = h.answered()
    return R.judge(sent, config["limits"]), len(sent)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--system", choices=sorted(FACTORIES),
                    default="degraded")
    args = ap.parse_args(argv)
    try:
        files = R.load_cell(args.workload)
        sys.path.insert(0, os.path.join(R.ROOT, "src"))
        jax = R.setup_jax()
        device, _, _ = R.device_info(jax, int(files[1]["chips"]))
    except R.Refused as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    largest, smallest = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        checks, n = readings(files, seed, args.system)
        values = {k: v for k, (v, _) in checks.items()}
        print(json.dumps({"seed": seed, "system": args.system,
                          "answers": n, "checks": values}), flush=True)
        for k, v in values.items():
            largest[k] = max(largest.get(k, v), v)
            smallest[k] = min(smallest.get(k, v), v)
    print(json.dumps({"workload": args.workload, "system": args.system,
                      "device": device, "largest": largest,
                      "smallest": smallest}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
