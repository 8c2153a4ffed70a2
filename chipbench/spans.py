"""The benchmark's own spans and counters.

Spans wrap, for the length of a traced window, the module attributes
through which ``serve/partition_service.py`` calls each layer, and
nothing inside the program.  Each span times its call on the host clock and writes a
``jax.profiler.TraceAnnotation`` named ``bench.<span>``, so that the
trace reduction can name the idle gaps by what the host was doing.
Nested calls inside a layer are not wrapped: they count to the layer
that made them.

The compile counter counts JAX's backend-compile events
(``/jax/core/compile/backend_compile_duration``): one per program that
JAX builds or loads from its persistent cache, none for a program that
a process already holds.
"""
from __future__ import annotations

import contextlib
import time
import types
from typing import Callable, Dict, List

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# span name -> the attributes of ``serve/partition_service.py`` wrapped
# for it; "instances_mod.refine_grouped" is reached through the service's
# own ``instances_mod`` binding, so only the service's calls are wrapped
SERVICE_SPANS: Dict[str, List[str]] = {
    "coarsen": ["build_hierarchy"],
    "initial": ["initial_partition_population"],
    "refine": ["instances_mod.refine_grouped"],
}


class Spans:
    """Per-span host durations for one window; with ``annotate``, each
    span is also a ``TraceAnnotation`` in the profiler's trace."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.durations: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        note = (jax.profiler.TraceAnnotation(f"bench.{name}")
                if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with note:
            try:
                yield
            finally:
                self.durations.setdefault(name, []).append(
                    time.perf_counter() - t0)

    def total(self, name: str) -> float:
        return float(sum(self.durations.get(name, ())))

    def count(self, name: str) -> int:
        return len(self.durations.get(name, ()))

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped


class _Rebound(types.SimpleNamespace):
    """A stand-in for a module binding: the wrapped attributes are its
    own, everything else is read from the module."""

    def __init__(self, module, **attrs):
        super().__init__(**attrs)
        self._module = module

    def __getattr__(self, name):
        return getattr(self._module, name)


@contextlib.contextmanager
def instrument(module, plan: Dict[str, List[str]], spans: Spans):
    """Wrap ``module``'s bindings named in ``plan`` for the duration of
    the block, and put the originals back after it."""
    by_head: Dict[str, Dict[str, str]] = {}
    for span, paths in plan.items():
        for path in paths:
            head, _, leaf = path.rpartition(".")
            by_head.setdefault(head, {})[leaf] = span
    saved = {}
    try:
        for head, leaves in by_head.items():
            if not head:
                for leaf, span in leaves.items():
                    saved[leaf] = getattr(module, leaf)
                    setattr(module, leaf, spans.wrap(span, saved[leaf]))
                continue
            inner = saved[head] = getattr(module, head)
            setattr(module, head, _Rebound(inner, **{
                leaf: spans.wrap(span, getattr(inner, leaf))
                for leaf, span in leaves.items()}))
        yield spans
    finally:
        for attr, orig in saved.items():
            setattr(module, attr, orig)


class CompileCounter:
    """Counts backend-compile events while ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event == COMPILE_EVENT:
            self.count += 1
