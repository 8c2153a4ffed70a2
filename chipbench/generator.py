"""Requests made from a configuration, a traffic mix and a seed.

The configuration names the deployment's netlist (``instance``: the
published cell and net counts and the generator's parameters) and the
``k`` and ``eps`` it is partitioned at.  The netlist generator is a copy
of ``_modular_netlist`` in the program's ``data/hypergraphs.py`` as it
stood when this benchmark was written, so that the yardstick does not
move when the program's copy does.  Instances are plain numpy CSR
(``Instance``); the system adapter wraps them in the program's types.

One general generator reads every mix (``traffic/<name>.json``):

* ``clients`` callers in a closed loop, each sending its next request
  when its answer arrives;
* ``requests`` distinct requests: the netlist with a program seed each,
  drawn from the mix's fixed ``request_seed``.  The callers cycle
  through them in an order drawn from the run's ``--seed``.  Every seed
  sends the same requests, so every run does the same work in another
  order: the partitioner's work differs from one program seed to the
  next by more than a run's own noise.
"""
from __future__ import annotations

import dataclasses
import itertools
import zlib
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Instance:
    """One netlist: a CSR hypergraph with unit weights, partitioned
    into ``k`` blocks under the imbalance ``eps``."""
    name: str
    n: int
    m: int
    pins: np.ndarray          # [P] int32
    offsets: np.ndarray       # [m+1] int64
    vertex_weights: np.ndarray
    edge_weights: np.ndarray
    k: int
    eps: float


@dataclasses.dataclass(frozen=True)
class Request:
    """One partitioning request: a netlist and the program seed the
    partitioner solves it with."""
    name: str
    inst: Instance
    seed: int


def modular_netlist(n: int, m: int, seed: int, n_modules: int,
                    p_local: float, fanout_tail: float
                    ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Rent-style modular netlist (copy of ``_modular_netlist``).
    Returns ``(pins, offsets, m)`` after dropping nets of one pin."""
    rng = np.random.default_rng(seed)
    module = rng.integers(0, n_modules, size=n)
    order = np.argsort(module, kind="stable")
    mod_of = module[order]
    starts = np.searchsorted(mod_of, np.arange(n_modules))
    counts = np.bincount(mod_of, minlength=n_modules)

    u = rng.random(m)
    sizes = np.where(
        u < 0.55, 2,
        np.where(u < 0.8, 3,
                 np.where(u < 0.92, 4,
                          np.minimum(3 + rng.pareto(fanout_tail, m).astype(
                              np.int64), 48))))
    sizes = np.maximum(sizes, 2).astype(np.int64)

    edges = []
    local = rng.random(m) < p_local
    driver_mod = rng.integers(0, n_modules, size=m)
    for e in range(m):
        s = int(sizes[e])
        md = int(driver_mod[e])
        if local[e] and counts[md] >= s:
            base = starts[md] + rng.integers(0, max(counts[md] - s + 1, 1))
            pins = order[base: base + s]
        else:
            k_span = min(1 + rng.poisson(1.2), n_modules)
            mods = rng.choice(n_modules, size=max(k_span, 1), replace=False)
            pool = np.concatenate([
                order[starts[mm]: starts[mm] + counts[mm]] for mm in mods
                if counts[mm] > 0]) if len(mods) else np.arange(n)
            if len(pool) < s:
                pool = np.arange(n)
            pins = rng.choice(pool, size=s, replace=False)
        edges.append(np.unique(pins))
    edges = [e for e in edges if len(e) >= 2]
    pins = np.concatenate(edges).astype(np.int32)
    offsets = np.concatenate(
        [[0], np.cumsum([len(e) for e in edges])]).astype(np.int64)
    return pins, offsets, len(edges)


def make_instance(config: Dict) -> Instance:
    """The configuration's netlist at its ``k`` and ``eps``."""
    spec = config["instance"]
    n = int(spec["n"])
    pins, offsets, m = modular_netlist(
        n, int(spec["m"]), seed=int(spec["netlist_seed"]),
        n_modules=int(spec["n_modules"]), p_local=float(spec["p_local"]),
        fanout_tail=float(spec["fanout_tail"]))
    return Instance(name=spec["name"], n=n, m=m, pins=pins, offsets=offsets,
                    vertex_weights=np.ones(n, np.float32),
                    edge_weights=np.ones(m, np.float32),
                    k=int(config["k"]), eps=float(config["eps"]))


def _seed(*parts) -> int:
    """A 31-bit seed from the run seed and a label: crc32, so every
    process draws the same (builtin ``hash`` is salted per process)."""
    return zlib.crc32(":".join(str(p) for p in parts).encode()) % (2 ** 31)


def requests(config: Dict, mix: Dict, seed: int) -> List[Request]:
    """The mix's distinct requests, in the order ``seed`` draws."""
    inst = make_instance(config)
    reqs = [Request(f"{inst.name}-{j}", inst,
                    _seed("request", mix["request_seed"], j))
            for j in range(int(mix["requests"]))]
    order = np.random.default_rng(_seed("order", seed)).permutation(
        len(reqs))
    return [reqs[i] for i in order]


def cycle(reqs: Sequence[Request]) -> Iterator[Request]:
    """The requests in their order, endlessly."""
    return itertools.cycle(reqs)
