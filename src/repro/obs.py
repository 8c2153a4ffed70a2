"""In-program spans and work counters of the served path.

One process-global recorder, off by default::

    from repro import obs
    obs.reset(); obs.enable()
    ...                       # solve
    obs.disable()
    obs.snapshot()  # {"spans": {name: {count, total_s, self_s}},
                    #  "counters": {name: value}}

When off, ``span`` returns one shared null context after a single
boolean check and ``count`` returns at once: nothing is recorded and no
profiler annotation is made.  When on, ``span(name, **attrs)`` enters a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (so a profiled
run shows the span on the device trace's own clock) and keeps
``(name, start_ns, end_ns, parent, attrs)`` in a bounded in-memory
list, ``parent`` being the index of the enclosing open span (-1 at the
top).  Spans past ``MAX_SPANS`` are dropped and counted under
``obs.dropped``.  Counters are taken only from arrays already on the
host, so recording never adds a device sync.  Spans nest per process:
the served path runs on one thread.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import numpy as np

MAX_SPANS = 1 << 17

_on = False
_NULL = contextlib.nullcontext()
_records: List[list] = []  # [name, start_ns, end_ns, parent, attrs]
_stack: List[int] = []     # indices of the open spans, innermost last
_counters: Dict[str, float] = {}


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    """Forget every span and counter (call it with no span open)."""
    _records.clear()
    _stack.clear()
    _counters.clear()


class _Span:
    __slots__ = ("idx", "note")

    def __init__(self, name: str, attrs: dict):
        import jax
        self.note = jax.profiler.TraceAnnotation(f"repro.{name}", **attrs)
        self.note.__enter__()
        parent = _stack[-1] if _stack else -1
        if len(_records) < MAX_SPANS:
            self.idx = len(_records)
            _records.append([name, time.perf_counter_ns(), None, parent,
                             attrs])
            _stack.append(self.idx)
        else:  # dropped: its children nest under its parent
            self.idx = -1
            _counters["obs.dropped"] = _counters.get("obs.dropped", 0) + 1
            _stack.append(parent)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self.idx >= 0:
            _records[self.idx][2] = time.perf_counter_ns()
        _stack.pop()
        self.note.__exit__(*exc)
        return False


def span(name: str, **attrs):
    """A context manager timing ``name`` (a null one when off)."""
    if not _on:
        return _NULL
    return _Span(name, attrs)


def count(name: str, n=1, times=None) -> None:
    """Add ``n`` to counter ``name`` -- a number, or a host array whose
    sum is added; with ``times``, the sum of ``n * times``.  The
    arithmetic happens only when the recorder is on."""
    if not _on:
        return
    v = np.sum(n if times is None else np.multiply(n, times))
    _counters[name] = _counters.get(name, 0) + v.item()


def snapshot() -> Dict[str, Dict]:
    """Per span name its count, total seconds and self seconds (total
    less the time its child spans cover), and the counters."""
    child_ns = [0] * len(_records)
    for name, t0, t1, parent, _ in _records:
        if t1 is not None and parent >= 0:
            child_ns[parent] += t1 - t0
    spans: Dict[str, Dict] = {}
    for i, (name, t0, t1, _, _) in enumerate(_records):
        if t1 is None:
            continue
        s = spans.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += (t1 - t0) * 1e-9
        s["self_s"] += (t1 - t0 - child_ns[i]) * 1e-9
    return {"spans": spans, "counters": dict(_counters)}

