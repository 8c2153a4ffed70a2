"""The system under test, behind the interface the load loop drives.

A configuration's ``entry`` names its adapter.  An adapter takes
requests (``submit``), advances its work (``step``), and hands back the
answers that ``step`` finished as ``Answer``s stamped with the host
clock.  Each request is given to the program as a new ``Hypergraph``
object, so no request finds arrays that an earlier one left on the
device.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from .spans import SERVICE_SPANS


@dataclasses.dataclass
class Answer:
    rid: str
    part: Optional[np.ndarray]
    cut: Optional[float]
    status: str
    finished_s: float


def to_hypergraph(inst):
    from repro.core.hypergraph import Hypergraph
    return Hypergraph(n=inst.n, m=inst.m, pins=inst.pins,
                      edge_offsets=inst.offsets,
                      vertex_weights=inst.vertex_weights,
                      edge_weights=inst.edge_weights)


class ServiceSystem:
    """``PartitionService``: ``step`` is one service tick."""

    # the layers' spans: the module whose bindings are wrapped, and how
    span_plan = ("repro.serve.partition_service", SERVICE_SPANS)
    step_span = "tick"

    def __init__(self, config: Dict, service: Optional[Dict] = None):
        from repro.serve import PartitionRequest, PartitionService
        self._request = PartitionRequest
        self.svc = PartitionService(**(config["service"] if service is None
                                       else service))
        self._seen = 0

    @property
    def busy(self) -> bool:
        return self.svc.busy

    def submit(self, rid: str, req) -> None:
        inst = req.inst
        self.svc.submit(self._request(
            name=rid, hg=to_hypergraph(inst), k=inst.k, eps=inst.eps,
            seed=req.seed))

    def step(self) -> List[Answer]:
        self.svc.step()
        # results keep their insertion order and each name appears once
        results = list(self.svc.results.values())
        fresh = results[self._seen:]
        self._seen = len(results)
        return [Answer(r.name, r.part, r.cut, r.status, r.finished_s)
                for r in fresh]


SYSTEMS = {"PartitionService": ServiceSystem}


def make_system(config: Dict):
    try:
        cls = SYSTEMS[config["entry"]]
    except KeyError:
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"known entry: {config.get('entry')!r}; known: "
                         f"{sorted(SYSTEMS)}") from None
    return cls(config)
