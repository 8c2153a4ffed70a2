#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  Everything
that belongs to it is found by name: its configuration in
``chipbench/configs/<config>.json``, its traffic mix in
``chipbench/traffic/<traffic>.json``, and each per-layer metric's reader
in ``chipbench/metrics/<metric>.py`` or, for ``<family>.<cell tag>``,
``chipbench/metrics/<family>.py``.

A run puts the mix's requests in an order drawn from ``--seed``, builds
the system, and sends every distinct request once (set-up,
``setup_s``: the window then sends nothing whose programs were not
built).  Then a closed loop of callers drives the system for
``--seconds`` and on to the end of the cycle through the requests, so
the window holds whole cycles of whole solves -- with no profiler
(``--trace 0``: the cell's end-to-end metrics) or under the profiler
with the layers' spans, the profiler recording the window's first
``TRACE_S`` seconds (``--trace 1``: its per-layer metrics).  After the
window it reads the device's peak memory and judges every answer
against the plain reference (``reference.py``).  The numbers it
compared go to standard error with their limits, and into the result
line's last key.

With no TPU, with fewer chips than the cell asks for, or with a device
that the peaks table does not know, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
# fixed paths inside the checkout: the compile cache's path is part of
# its key, so it must not move between runs
CACHE_DIR = os.path.join(ROOT, ".chipbench", "jax_cache")
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
# a solve still running this long after the window's nominal end is
# given up and counted as missing (a cycle of two solves takes about
# 80 s on a v5e, and a traced run's profiler stop adds about 50 s)
GRACE_S = 240.0
# a traced run records the first seconds of its window: a whole window's
# trace takes longer to write and read back than a run may last
TRACE_S = 12.0


class Refused(Exception):
    """The run cannot produce a result here; exit non-zero."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; known: "
                      f"{sorted(cells)}")
    cell = cells[name]
    config = load_json(os.path.join(HERE, "configs",
                                    f"{cell['config']}.json"))
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return bench, cell, config, mix


def cell_metrics(bench, cell, kind: str):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def find_reader(metric: str):
    """The reader module of a per-layer metric: ``metrics/<name>.py``,
    else ``metrics/<family>.py`` for ``<family>.<tag>``."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(HERE, "metrics", f"{stem}.py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise Refused(f"no reader for per-layer metric {metric!r} under "
                  "chipbench/metrics/")


def setup_jax():
    """Point JAX's persistent compile cache at the checkout's fixed
    directory and cache every program, however fast it compiles."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def device_info(jax, chips: int, allow_cpu: bool = False):
    from chipbench.peaks import UnknownDevice, peaks_for
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" and not allow_cpu:
        raise Refused(f"no TPU: JAX sees platform {info['platform']!r}")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chip(s); JAX sees "
                      f"{info['count']}")
    try:
        peaks = None if allow_cpu else peaks_for(info["kind"])
    except UnknownDevice as e:
        raise Refused(str(e)) from None
    return info, peaks, devs[:chips]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Harness:
    """One run: set-up, the window, the check."""

    def __init__(self, cell, config, mix, seed: int, seconds: float,
                 trace: bool, system_factory=None):
        from chipbench import generator
        from chipbench.spans import CompileCounter, Spans
        from chipbench.systems import make_system
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.requests = generator.requests(config, mix, seed)
        self.system = (system_factory or make_system)(config)
        self.spans = Spans(annotate=trace)
        self.compiles = CompileCounter()
        self.compiles.active = True  # set-up's compiles, printed apart
        self.setup_compiles = 0
        self.records = {}
        self._n = 0
        self._note = None

    def _submit(self, req) -> None:
        rid = f"{req.name}#{self._n}"
        self._n += 1
        self.records[rid] = {"inst": req.inst, "answer": None}
        self.system.submit(rid, req)

    def _step(self):
        with self.spans.span(self.system.step_span):
            answers = self.system.step()
        for a in answers:
            if a.rid in self.records:
                self.records[a.rid]["answer"] = a
        self._trace_due()
        return answers

    def _start_trace(self) -> None:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        self._note = jax.profiler.TraceAnnotation("bench.window")
        self._note.__enter__()
        self._trace_t0 = time.perf_counter()

    def _trace_due(self, force: bool = False) -> None:
        """Stop the profiler once it has recorded ``TRACE_S`` seconds
        (between steps, so that no span is cut)."""
        if self._note is None or not (
                force or time.perf_counter() - self._trace_t0 >= TRACE_S):
            return
        import jax
        self._note.__exit__(None, None, None)
        self._note = None
        jax.profiler.stop_trace()

    def _drive(self, seconds: float, sends: float = math.inf):
        """The closed loop: ``clients`` callers, each sending the next
        request when its answer arrives, until ``seconds`` have passed
        and the last cycle through the requests is whole, or until
        ``sends`` requests were sent; then the solves in flight run to
        their end (for at most ``GRACE_S`` more past ``seconds``).
        Returns the window's start and end on the host clock."""
        from chipbench import generator
        order = generator.cycle(self.requests)
        t0 = time.perf_counter()
        end = t0 + seconds
        sent = 0

        def more() -> bool:
            return sent < sends and (time.perf_counter() < end
                                     or sent % len(self.requests) != 0)

        for _ in range(int(self.mix["clients"])):
            if more():
                self._submit(next(order))
                sent += 1
        while self.system.busy and time.perf_counter() < end + GRACE_S:
            for _ in self._step():
                if more():
                    self._submit(next(order))
                    sent += 1
        return t0, time.perf_counter()

    def warm_up(self) -> None:
        """Every distinct request through the system once, as the
        window sends them: the window's requests repeat these, so it
        builds no program that set-up did not."""
        self._drive(math.inf, sends=len(self.requests))
        self.records = {}

    def window(self):
        from chipbench.spans import Spans, instrument
        plan = self.system.span_plan if self.trace else None
        module = importlib.import_module(plan[0]) if plan else None
        self.spans.durations = {}  # the window's spans only
        self.setup_compiles, self.compiles.count = self.compiles.count, 0
        self.compiles.active = True
        try:
            with instrument(module, plan[1] if plan else {}, self.spans):
                if self.trace:
                    self._start_trace()
                self.t0, self.t_end = self._drive(self.seconds)
        finally:
            self.compiles.active = False
            self._trace_due(force=True)
        self.window_spans, self.spans = self.spans, Spans(annotate=False)

    def answered(self):
        """(every request the window sent, those answered ``ok``)."""
        sent = list(self.records.values())
        ok = [r for r in sent if r["answer"] is not None
              and r["answer"].status == "ok"]
        return sent, ok


def judge(records, limits, reference_seed: int = 0):
    """Every answer against the exact gate, the plain reference's cut
    and the reference's single-vertex moves.  Returns {check: (value,
    limit)}."""
    from chipbench import reference
    totals = {"missing": 0, "bad_ids": 0, "over_cap": 0, "cut_mismatch": 0}
    worst, worst_gain = 0.0, 0.0
    ref_cut, gains = {}, {}
    for r in records:
        inst, a = r["inst"], r["answer"]
        if a is None or a.part is None or a.status != "ok":
            totals["missing"] += 1
            continue
        gate = reference.check_partition(inst, a.part, a.cut)
        for key in ("bad_ids", "over_cap", "cut_mismatch"):
            totals[key] += gate[key]
        if gate["recomputed"] is None:
            continue
        if inst.name not in ref_cut:
            part = reference.reference_partition(inst, reference_seed)
            ref_cut[inst.name] = reference.numpy_cut(
                inst.pins, inst.offsets, inst.edge_weights, part)
        worst = max(worst, gate["recomputed"] / max(ref_cut[inst.name], 1.0))
        # a repeated request gives the same answer: move its vertices once
        key = (inst.name, np.asarray(a.part).tobytes())
        if key not in gains:
            gains[key] = reference.move_gain(inst, a.part, reference_seed)
        worst_gain = max(worst_gain, gains[key])
    checks = {k: (float(v), float(limits[k])) for k, v in totals.items()}
    checks["quality"] = (worst, float(limits["quality"]))
    checks["fm_gain"] = (worst_gain, float(limits["fm_gain"]))
    return checks


def end_to_end(h: Harness, setup_s: float):
    """Every end-to-end number this harness knows, by name."""
    _, ok = h.answered()
    return {"setup_s": setup_s,
            "solve_s": (h.t_end - h.t0) / max(len(ok), 1)}


class Run:
    """What a per-layer reader may read."""

    def __init__(self, h: Harness, reduced):
        self.window_s = h.t_end - h.t0
        self.solves = len(h.answered()[1])
        self.spans = h.window_spans
        self.compiles = h.compiles.count
        self.trace = reduced


def run(args, allow_cpu: bool = False, system_factory=None,
        cell_files=None) -> dict:
    """One run of ``args.workload``.  ``allow_cpu``, ``system_factory``
    and ``cell_files`` (``(bench, cell, config, mix)``) are for the
    benchmark's own tests, which drive a run without the chip."""
    bench, cell, config, mix = cell_files or load_cell(args.workload)
    kind = "per_layer" if args.trace else "end_to_end"
    wanted = cell_metrics(bench, cell, kind)
    readers = {m["name"]: find_reader(m["name"]) for m in wanted} \
        if args.trace else {}
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program under {src}")
    sys.path.insert(0, src)
    jax = setup_jax()
    device, peaks, used = device_info(jax, int(cell["chips"]), allow_cpu)

    h = Harness(cell, config, mix, args.seed, args.seconds,
                bool(args.trace), system_factory)
    h.warm_up()
    setup_s = time.perf_counter() - T_START
    h.window()
    device["memory_peak_bytes"] = memory_peak(used)

    reduced = None
    if args.trace:
        from chipbench import trace as trace_mod
        path = trace_mod.find_xplane(TRACE_DIR)
        if path is None:
            raise Refused("the profiler wrote no trace")
        reduced = trace_mod.reduce_events(trace_mod.load_events(path))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s

    sent, ok = h.answered()
    steps = sorted(h.window_spans.durations.get(h.system.step_span, []))
    print(f"[chipbench] {cell['name']} seed={args.seed} on {device}: "
          f"window {h.t_end - h.t0:.3f}s, {len(sent)} requests sent, "
          f"{len(ok)} answered ok, {h.compiles.count} compiles in the "
          f"window; {len(steps)} {h.system.step_span}s, median "
          f"{(steps[len(steps) // 2] if steps else 0.0):.6f}s max "
          f"{(steps[-1] if steps else 0.0):.6f}s; set-up {setup_s:.3f}s "
          f"with {h.setup_compiles} compiles",
          file=sys.stderr, flush=True)
    system, h.system = h.system, None
    del system
    gc.collect()
    t_check = time.perf_counter()
    checks = judge(sent, config["limits"])
    print(f"[chipbench] the check took {time.perf_counter() - t_check:.3f}s",
          file=sys.stderr, flush=True)

    metrics = {}
    if args.trace:
        r = Run(h, reduced)
        for m in wanted:
            value = readers[m["name"]].read(r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(h, setup_s)
        for m in wanted:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    correct = all(v <= lim for v, lim in checks.values())
    result = {"correct": correct, "attempted": len(sent),
              "failed": len(sent) - len(ok),
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        result = run(args)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
