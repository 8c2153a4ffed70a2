"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU at a small size (the cell's configuration with a netlist
of a few hundred cells, short windows), with one fault planted:

* the controls -- the plain reference with its refinement taken out in
  the program's place (``control.ControlSystem``), and the program's own
  path with refinement switched off (``control.degraded``);
* a step that returns its state unchanged -- every grouped refinement
  hands back its input, or a service tick that advances nothing;
* half of the batch left out -- every second request that a service
  tick finishes is dropped, its answer never sent;
* an answer altered where it is produced -- one vertex moved to the
  next block after the program computed the cut.

A cell on one chip has no exchange between chips to leave out.

    python -m pytest chipbench/tests -q
"""
import os

import numpy as np
import pytest

from chipbench import run as R
from chipbench.control import ControlSystem, readings

CELL = "ibm01-k4-svc"


def tiny(requests=2, n=800):
    """The cell with a small netlist of the configuration's kind."""
    bench, cell, config, mix = R.load_cell(CELL)
    config = dict(config, instance=dict(config["instance"], n=n,
                                        m=n * 5 // 4, n_modules=8))
    return bench, cell, config, dict(mix, requests=requests)


def run_tiny(files, seconds=1.0, seed=1, system_factory=None):
    args = R.parse_args(["--workload", files[1]["name"], "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0"])
    return R.run(args, allow_cpu=True, system_factory=system_factory,
                 cell_files=files)


def test_reference_control_is_not_correct():
    res = run_tiny(tiny(), system_factory=ControlSystem)
    checks = res["checks"]
    assert res["correct"] is False
    assert checks["quality"]["value"] > checks["quality"]["limit"]
    assert checks["fm_gain"]["value"] > checks["fm_gain"]["limit"]
    for k in ("missing", "bad_ids", "over_cap", "cut_mismatch"):
        assert checks[k]["value"] == 0.0


def test_degraded_control_fails_the_refinement_guarantee():
    files = tiny()
    checks, n = readings(files, 7, "degraded")
    assert n == 2
    assert checks["fm_gain"][0] > checks["fm_gain"][1]
    for k in ("missing", "bad_ids", "over_cap", "cut_mismatch"):
        assert checks[k][0] == 0.0


def test_sound_program_is_correct():
    res = run_tiny(tiny())
    assert res["correct"] is True and res["attempted"] > 0
    assert res["failed"] == 0
    assert res["metrics"]["solve_s"]["value"] > 0


def test_refinement_returning_its_state_unchanged(monkeypatch):
    import jax.numpy as jnp
    from repro.core import instances, metrics, refine

    def unchanged(entries, *args, **kwargs):
        out = []
        for e in entries:
            hga, k = e[0], e[2]
            parts = np.array(refine.pad_parts(np.asarray(e[1]), hga.n_pad))
            out.append((parts, np.asarray([float(metrics.cutsize(
                hga, jnp.asarray(p), k)) for p in parts])))
        return out

    monkeypatch.setattr(instances, "refine_grouped", unchanged)
    # the cell's own netlist: at a few thousand cells the coarsest
    # level's refined start is already near a local optimum at the finest
    bench, cell, config, mix = R.load_cell(CELL)
    res = run_tiny((bench, cell, config, dict(mix, requests=1)))
    assert res["correct"] is False
    assert res["checks"]["fm_gain"]["value"] > \
        res["checks"]["fm_gain"]["limit"]


def test_tick_returning_its_state_unchanged(monkeypatch):
    from repro.serve import PartitionService
    window = R.Harness.window

    def broken_window(self):  # set-up ran sound; the window's ticks not
        monkeypatch.setattr(PartitionService, "step", lambda svc: 0)
        return window(self)

    monkeypatch.setattr(R.Harness, "window", broken_window)
    monkeypatch.setattr(R, "GRACE_S", 2.0)
    res = run_tiny(tiny())
    assert res["correct"] is False
    assert res["checks"]["missing"]["value"] == res["attempted"]


def test_half_of_the_batch_left_out(monkeypatch):
    from repro.serve import PartitionService
    orig = PartitionService._finish
    count = {"n": 0}

    def finish_half(self, s, parts, cuts, degraded=False):
        count["n"] += 1
        if count["n"] % 2 == 0:
            s.vacate()
            return None
        return orig(self, s, parts, cuts, degraded)

    monkeypatch.setattr(PartitionService, "_finish", finish_half)
    res = run_tiny(tiny(), seconds=5.0)
    assert res["correct"] is False
    assert res["checks"]["missing"]["value"] > 0


def _moved(part, k):
    part = np.array(part, copy=True)
    part[0] = (part[0] + 1) % k
    return part


def test_answer_altered_in_the_service(monkeypatch):
    from repro.serve import PartitionService
    orig = PartitionService._finish

    def altered(self, s, parts, cuts, degraded=False):
        parts = np.stack([_moved(p, s.request.k) for p in np.asarray(parts)])
        return orig(self, s, parts, cuts, degraded)

    monkeypatch.setattr(PartitionService, "_finish", altered)
    res = run_tiny(tiny())
    assert res["correct"] is False
    assert res["checks"]["cut_mismatch"]["value"] > 0
