"""The in-program recorder (``repro.obs``): spans, counters, and what it
records on the served path."""
import glob

import numpy as np
import pytest

from repro import obs
from repro.data.hypergraphs import _modular_netlist
from repro.serve.partition_service import PartitionRequest, PartitionService


@pytest.fixture(autouse=True)
def _recorder_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


class _Clock:
    """A perf_counter_ns that advances 10 ns per reading."""

    def __init__(self):
        self.t = 0

    def __call__(self):
        self.t += 10
        return self.t


def _no_annotation(*args, **kwargs):
    raise AssertionError("the recorder made a TraceAnnotation while off")


def test_off_records_nothing_and_annotates_nothing(monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _no_annotation)
    a = obs.span("refine", n=8, k=4)
    b = obs.span("wait")
    assert a is b  # the one shared null context
    with a:
        obs.count("readbacks")
        obs.count("lp.lane_improved", np.ones(3, bool))
    assert obs.snapshot() == {"spans": {}, "counters": {}}
    assert obs._records == [] and obs._stack == []


def test_nesting_gives_parents_and_self_time(monkeypatch):
    monkeypatch.setattr(obs.time, "perf_counter_ns", _Clock())
    obs.enable()
    with obs.span("refine", n=64, k=4):          # t 10 .. 80
        with obs.span("refine.lp"):              # t 20 .. 50
            with obs.span("wait"):               # t 30 .. 40
                pass
        with obs.span("wait"):                   # t 60 .. 70
            pass
    assert [(r[0], r[3]) for r in obs._records] == [
        ("refine", -1), ("refine.lp", 0), ("wait", 1), ("wait", 0)]
    assert obs._records[0][4] == {"n": 64, "k": 4}
    spans = obs.snapshot()["spans"]
    assert spans["refine"]["count"] == 1
    assert spans["refine"]["total_s"] == pytest.approx(70e-9)
    assert spans["refine"]["self_s"] == pytest.approx(30e-9)   # 70 - 30 - 10
    assert spans["refine.lp"]["self_s"] == pytest.approx(20e-9)
    assert spans["wait"]["count"] == 2
    assert spans["wait"]["total_s"] == pytest.approx(20e-9)
    assert spans["wait"]["self_s"] == pytest.approx(20e-9)


def test_an_open_span_is_left_out_of_the_snapshot():
    obs.enable()
    with obs.span("service.tick", tick=1):
        with obs.span("wait"):
            pass
        snap = obs.snapshot()
    assert set(snap["spans"]) == {"wait"}
    assert set(obs.snapshot()["spans"]) == {"wait", "service.tick"}


def test_counters_add():
    obs.enable()
    obs.count("readbacks")
    obs.count("readbacks", 2)
    obs.count("lp.lane_improved", np.array([[True, False], [True, True]]))
    live = np.array([[True, True, False], [False, True, True]])
    used = np.array([3, 1])
    obs.count("lp.lane_attempts", live, used[:, None])
    c = obs.snapshot()["counters"]
    assert c == {"readbacks": 3, "lp.lane_improved": 3,
                 "lp.lane_attempts": 3 + 3 + 1 + 1}
    assert all(type(v) is int for v in c.values())


def test_reset_clears_everything():
    obs.enable()
    with obs.span("coarsen", req="a"):
        obs.count("readbacks")
    obs.reset()
    assert obs.snapshot() == {"spans": {}, "counters": {}}
    assert obs._records == [] and obs._stack == []


def test_the_bounded_list_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(obs, "MAX_SPANS", 2)
    obs.enable()
    with obs.span("refine"):
        for _ in range(3):
            with obs.span("wait"):
                pass
    snap = obs.snapshot()
    assert snap["spans"]["refine"]["count"] == 1
    assert snap["spans"]["wait"]["count"] == 1
    assert snap["counters"] == {"obs.dropped": 2}
    assert obs._stack == []


# -- the served path ---------------------------------------------------------
@pytest.fixture(scope="module")
def netlist():
    return _modular_netlist(400, 520, seed=7, n_modules=6, p_local=0.8,
                            fanout_tail=1.5)


def _solve(hg, name="r0", seed=3):
    svc = PartitionService(slots=1, alpha=2, lp_iters=4, shard="off")
    svc.submit(PartitionRequest(name=name, hg=hg, k=4, eps=0.08,
                                seed=seed))
    return svc.drain()[0]


def test_service_answers_are_identical_with_the_recorder_on(netlist):
    off = _solve(netlist)
    obs.enable()
    on = _solve(netlist)
    obs.disable()
    assert on.status == off.status == "ok"
    assert np.array_equal(on.part, off.part) and on.cut == off.cut
    snap = obs.snapshot()
    spans = snap["spans"]
    for name in ("service.tick", "coarsen", "initial", "refine",
                 "refine.stack", "refine.lp", "refine.fm", "wait"):
        assert spans[name]["count"] >= 1, name
    assert spans["coarsen"]["count"] == spans["initial"]["count"] == 1
    # every level refines once, and every level but the coarsest is
    # projected to first
    assert spans["refine"]["count"] == spans["project"]["count"] + 1
    assert spans["refine.lp"]["count"] == spans["refine"]["count"]
    by_name = {r[0]: r[4] for r in obs._records}
    for name in ("coarsen", "initial", "project"):
        assert by_name[name]["req"] == "r0"
    ticks = [r[4]["tick"] for r in obs._records if r[0] == "service.tick"]
    assert ticks == list(range(1, len(ticks) + 1))
    c = snap["counters"]
    assert c["readbacks"] == spans["wait"]["count"]
    assert 0 < c["lp.lane_improved"] <= c["lp.lane_attempts"]
    assert 0 <= c["fm.lane_accepted"] <= c["fm.lane_passes"]
    # the layer spans hold the refinement tiers
    assert spans["refine"]["total_s"] >= (spans["refine.lp"]["total_s"]
                                          + spans["refine.fm"]["total_s"])


def test_identical_solves_count_identically(netlist):
    counters = []
    for _ in range(2):
        obs.reset()
        obs.enable()
        _solve(netlist)
        obs.disable()
        snap = obs.snapshot()
        counters.append((snap["counters"],
                         {k: v["count"] for k, v in snap["spans"].items()}))
    assert counters[0] == counters[1]


def test_lp_and_fm_program_names_are_pinned(netlist, tmp_path):
    """The benchmark finds the LP and FM programs in a device trace by
    their jitted function's name (``lp_attempt``, ``fm_pass``): a rename
    fails here."""
    import jax
    from jax.profiler import ProfileData
    _solve(netlist)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    _solve(netlist)
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    modules = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                for key, value in e.stats:
                    if key == "hlo_module":
                        modules.add(value)
    assert "jit__lp_attempt_instances_impl" in modules, sorted(modules)
    assert "jit__fm_pass_instances_impl" in modules, sorted(modules)
