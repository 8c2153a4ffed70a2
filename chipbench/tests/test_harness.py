"""CPU checks of the benchmark's own arithmetic and plumbing.

    python -m pytest chipbench/tests -q

These sit outside the repository's ``testpaths``: they test the
yardstick, not the program.
"""
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import generator, reference
from chipbench import run as R
from chipbench import trace as T

ROOT = R.ROOT


# -- discovery -------------------------------------------------------------
def test_every_cell_finds_its_files_and_readers():
    bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        _, cell, config, mix = R.load_cell(w["name"])
        assert cell["config"] == config["name"]
        assert mix["loop"] == "closed"
        per_layer = R.cell_metrics(bench, cell, "per_layer")
        end_to_end = R.cell_metrics(bench, cell, "end_to_end")
        assert per_layer and "setup_s" in {m["name"] for m in end_to_end}
        for m in per_layer:
            assert callable(R.find_reader(m["name"]).read)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_reader_found_by_family_and_by_full_name(tmp_path, monkeypatch):
    monkeypatch.setattr(R, "HERE", str(tmp_path))
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "fam.py").write_text("def read(run): return 1.0\n")
    (tmp_path / "metrics" / "fam.special.py").write_text(
        "def read(run): return 2.0\n")
    assert R.find_reader("fam.any").read(None) == 1.0
    assert R.find_reader("fam.special").read(None) == 2.0
    with pytest.raises(R.Refused):
        R.find_reader("absent.any")


def test_unknown_workload_is_refused():
    with pytest.raises(R.Refused):
        R.load_cell("no-such-cell")


# -- refusals --------------------------------------------------------------
def _fake_jax(platform, kind, count):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * count)


def test_refuses_cpu_few_chips_and_unknown_kind():
    with pytest.raises(R.Refused, match="no TPU"):
        R.device_info(_fake_jax("cpu", "cpu", 1), 1)
    with pytest.raises(R.Refused, match="needs 4"):
        R.device_info(_fake_jax("tpu", "TPU v5 lite", 1), 4)
    with pytest.raises(R.Refused, match="no peaks"):
        R.device_info(_fake_jax("tpu", "TPU v99", 1), 1)
    info, peaks, used = R.device_info(_fake_jax("tpu", "TPU v5 lite", 4), 1)
    assert info["count"] == 4 and len(used) == 1
    assert peaks["flops_per_s"] == 197e12


def test_exits_nonzero_without_a_result_on_cpu_and_alone(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "ibm01-k4-svc", "--seed", "1", "--seconds",
            "1", "--trace", "0"]
    here = subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    assert here.returncode != 0 and here.stdout.strip() == ""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), alone)
    shutil.copytree(os.path.join(ROOT, "chipbench"), alone / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = subprocess.run([sys.executable, "chipbench/run.py", *args],
                         cwd=alone, env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0 and res.stdout.strip() == ""


# -- traffic ---------------------------------------------------------------
def _config():
    _, _, config, mix = R.load_cell("ibm01-k4-svc")
    return config, mix


def test_every_seed_sends_the_same_requests_in_its_own_order():
    config, mix = _config()
    config = dict(config, instance=dict(config["instance"], n=600, m=700))
    mix = dict(mix, requests=5)
    seed = 2 ** 33 + 12345
    a = generator.requests(config, mix, seed)
    b = generator.requests(config, mix, seed)
    others = [generator.requests(config, mix, seed + i) for i in range(1, 6)]
    assert len(a) == 5
    assert [r.name for r in a] == [r.name for r in b]
    assert [r.seed for r in a] == [r.seed for r in b]
    for c in others:
        assert sorted((r.name, r.seed) for r in c) == sorted(
            (r.name, r.seed) for r in a)
    assert any([r.name for r in c] != [r.name for r in a] for c in others)
    assert len({r.seed for r in a}) == 5
    assert all(0 <= r.seed < 2 ** 31 for r in a)
    # every request is the same netlist
    assert all(r.inst is a[0].inst for r in a)
    assert (a[0].inst.k, a[0].inst.eps) == (config["k"], config["eps"])


def test_netlist_is_the_programs_ibm01_stand_in():
    from repro.data.hypergraphs import ispd_like
    config, mix = _config()
    inst = generator.make_instance(config)
    hg = ispd_like("ibm01_like", 1.0)
    assert (inst.n, inst.m) == (hg.n, hg.m) == (12752, 14111)
    assert np.array_equal(inst.pins, np.asarray(hg.pins))
    assert np.array_equal(inst.offsets, np.asarray(hg.edge_offsets))


def test_cycle_repeats_the_requests_in_order():
    config, mix = _config()
    config = dict(config, instance=dict(config["instance"], n=600, m=700))
    reqs = generator.requests(config, dict(mix, requests=3), 5)
    order = generator.cycle(reqs)
    names = [next(order).name for _ in range(7)]
    assert sorted(names[:3]) == ["ibm01-0", "ibm01-1", "ibm01-2"]
    assert names[3:6] == names[:3] and names[6] == names[0]


def test_window_runs_whole_cycles(monkeypatch):
    """The closed loop keeps sending past ``seconds`` until the cycle
    through the requests is whole, then lets the solve in flight end."""
    from chipbench.systems import Answer

    class Slow:
        span_plan = None
        step_span = "tick"

        def __init__(self, config):
            self.queue = []

        @property
        def busy(self):
            return bool(self.queue)

        def submit(self, rid, req):
            self.queue.append(rid)

        def step(self):
            import time
            time.sleep(0.05)
            rid = self.queue.pop(0)
            return [Answer(rid, None, None, "ok", time.perf_counter())]

    config, mix = _config()
    config = dict(config, instance=dict(config["instance"], n=600, m=700))
    h = R.Harness({"name": "x"}, config, dict(mix, requests=3), 1, 0.12,
                  False, Slow)
    h._drive(0.12)
    assert len(h.records) == 3  # past 0.12 s after 3 sends, cycle whole
    h.records = {}
    h._drive(float("inf"), sends=2)
    assert len(h.records) == 2


# -- end-to-end arithmetic -------------------------------------------------
def _answer(finished, status="ok"):
    return types.SimpleNamespace(finished_s=finished, status=status,
                                 part=None, cut=None)


def test_solve_s_is_window_over_whole_solves():
    recs = {i: {"answer": _answer(3.0 * (i + 1))} for i in range(4)}
    recs[4] = {"answer": _answer(13.0, status="quarantined")}
    h = R.Harness.__new__(R.Harness)
    h.records, h.t0, h.t_end = recs, 0.0, 12.0
    values = R.end_to_end(h, 1.0)
    assert values["solve_s"] == pytest.approx(3.0)
    assert values["setup_s"] == 1.0


# -- the reference ---------------------------------------------------------
def _inst(n=400, m=520, k=8, seed=3, eps=0.08):
    config = {"instance": {"name": "x", "n": n, "m": m, "netlist_seed": seed,
                           "n_modules": 8, "p_local": 0.8,
                           "fanout_tail": 1.5}, "k": k, "eps": eps}
    return generator.make_instance(config)


def test_gate_catches_each_fault():
    inst = _inst()
    part = reference.reference_partition(inst, 0)
    cut = reference.numpy_cut(inst.pins, inst.offsets, inst.edge_weights,
                              part)
    ok = reference.check_partition(inst, part, cut)
    assert (ok["bad_ids"], ok["over_cap"], ok["cut_mismatch"]) == (0, 0, 0)
    bad = part.copy()
    bad[0] = inst.k
    assert reference.check_partition(inst, bad, cut)["bad_ids"] == 1
    heavy = np.zeros(inst.n, np.int64)
    assert reference.check_partition(inst, heavy, 0.0)["over_cap"] == 1
    assert reference.check_partition(inst, part, cut + 1)["cut_mismatch"] == 1
    assert reference.check_partition(inst, None, None)["bad_ids"] == 1


def test_fm_gain_matches_recomputed_cut():
    inst = _inst(seed=11)
    start = reference.random_partition(inst, 4)
    before = reference.numpy_cut(inst.pins, inst.offsets,
                                 inst.edge_weights, start)
    after_part = reference.fm_passes(inst, start, 4)
    after = reference.numpy_cut(inst.pins, inst.offsets, inst.edge_weights,
                                after_part)
    cap = reference.cap_of(inst.vertex_weights, inst.k, inst.eps)
    assert after < before
    assert np.bincount(after_part, minlength=inst.k).max() <= cap
    grown = reference.grow_blocks(inst, 4)
    assert np.bincount(grown, minlength=inst.k).max() <= cap


def test_judge_quality_is_worst_ratio_to_the_reference():
    inst = _inst()
    ref = reference.reference_partition(inst, 0)
    ref_cut = reference.numpy_cut(inst.pins, inst.offsets,
                                  inst.edge_weights, ref)
    rnd = reference.random_partition(inst, 1)
    rnd_cut = reference.numpy_cut(inst.pins, inst.offsets,
                                  inst.edge_weights, rnd)
    def ans(part, cut, status="ok"):
        return types.SimpleNamespace(part=part, cut=cut, status=status)

    recs = [{"inst": inst, "answer": ans(ref, ref_cut)},
            {"inst": inst, "answer": ans(rnd, rnd_cut)},
            {"inst": inst, "answer": None},
            {"inst": inst, "answer": ans(None, None, "timed_out")}]
    limits = {"missing": 0, "bad_ids": 0, "over_cap": 0, "cut_mismatch": 0,
              "quality": 2.0, "fm_gain": 0.005}
    checks = R.judge(recs, limits)
    assert checks["quality"][0] == pytest.approx(rnd_cut / ref_cut)
    assert checks["fm_gain"][0] == pytest.approx(
        reference.move_gain(inst, rnd, 0))
    assert checks["fm_gain"][0] > 0.2
    assert checks["missing"] == (2.0, 0.0)
    assert checks["over_cap"] == (0.0, 0.0)


def test_move_gain_is_nought_at_a_local_optimum():
    inst = _inst(seed=5)
    part = reference.reference_partition(inst, 0)
    assert reference.move_gain(inst, part, 0) == 0.0


# -- the trace reduction ---------------------------------------------------
def _ev(plane, name, start, dur, line="l"):
    return T.Event(plane, line, name, float(start), float(dur))


def test_reduction_arithmetic_on_synthetic_events():
    dev, host = "/device:TPU:0", T.HOST_PLANE
    events = [
        _ev(host, "bench.window", 100, 1000),
        _ev(host, "bench.tick", 100, 900),
        _ev(host, "bench.refine", 300, 200),
        _ev(dev, "fusion.1", 50, 100, T.OPS_LINE),    # clipped to 100..150
        _ev(dev, "fusion.1", 400, 50, T.OPS_LINE),
        _ev(dev, "rating", 420, 80, T.OPS_LINE),      # overlaps: union
        _ev(dev, "fusion.2", 1050, 200, T.OPS_LINE),  # clipped to ..1100
    ]
    red = T.reduce_events(events)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx((50 + 100 + 50) * 1e-9)
    assert red.idle_share == pytest.approx(0.8)
    assert dict(red.device_ops) == pytest.approx(
        {"fusion.1": 100e-9, "rating": 80e-9, "fusion.2": 50e-9})
    # gaps: 150..400 (mid 275: tick), 500..1050 (mid 775: tick)
    assert dict(red.idle_gaps) == pytest.approx({"bench.tick": 800e-9})


def test_host_timeline_names_the_innermost_span():
    h = T.HOST_PLANE
    segs = T.host_timeline([_ev(h, "bench.a", 0, 100),
                            _ev(h, "bench.b", 10, 20),
                            _ev(h, "bench.c", 50, 10)])
    assert segs == [(0, 10, "bench.a"), (10, 30, "bench.b"),
                    (30, 50, "bench.a"), (50, 60, "bench.c"),
                    (60, 100, "bench.a")]


def test_host_spans_are_read_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from chipbench.spans import Spans
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(64)
    f(x).block_until_ready()
    spans = Spans(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    with spans.span("window"):
        with spans.span("tick"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = T.load_events(T.find_xplane(str(tmp_path)))
    names = sorted(e.name for e in events)
    assert names == ["bench.tick", "bench.window"]
    assert spans.count("tick") == 1 and spans.total("window") > 0
