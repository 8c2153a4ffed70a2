"""The plain reference: what a partition must satisfy, and a plain
partitioner to judge its quality by.  numpy and the standard library
only; nothing of the program is imported.

* ``numpy_cut`` and ``check_partition`` are copies of the gate in the
  program's ``chip_smoke.py``: block ids in ``[0, k)``, every block
  within the ``(1 + eps) * ceil(W / k)`` cap, and the cut-net weight
  recomputed from the pin list.
* ``reference_partition`` is a plain k-way partitioner of the same
  objective under the same cap: greedy growth of k blocks over shared
  nets, then passes of single-vertex moves of positive cut gain that
  keep the cap (Fiduccia-Mattheyses gains, no multilevel, no
  population).  Deterministic in its seed.
* ``move_gain`` runs those passes from an answer: the share of its cut
  that single-vertex moves still remove, 0 for an answer that
  refinement left at a local optimum.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def numpy_cut(pins: np.ndarray, offsets: np.ndarray,
              edge_weights: np.ndarray, part: np.ndarray) -> float:
    """Cut-net weight recomputed from the pin list alone."""
    blk = np.asarray(part)[pins]
    starts = offsets[:-1]
    cut = np.minimum.reduceat(blk, starts) != np.maximum.reduceat(blk, starts)
    return float(np.asarray(edge_weights)[cut].sum())


def cap_of(vertex_weights: np.ndarray, k: int, eps: float) -> float:
    return float((1.0 + eps) * np.ceil(np.sum(vertex_weights) / k))


def check_partition(inst, part, cut) -> Dict:
    """The exact gate of one answer.  Returns the number of faults of
    each kind (0 or 1) and the recomputed cut; never raises, so a
    wrong answer counts instead of ending the run."""
    out = {"bad_ids": 0, "over_cap": 0, "cut_mismatch": 0, "recomputed": None}
    if part is None:
        out.update(bad_ids=1, over_cap=1, cut_mismatch=1)
        return out
    part = np.asarray(part)
    if part.shape != (inst.n,) or part.min() < 0 or part.max() >= inst.k:
        out.update(bad_ids=1, over_cap=1, cut_mismatch=1)
        return out
    bw = np.bincount(part, weights=inst.vertex_weights, minlength=inst.k)
    if bw.max() > cap_of(inst.vertex_weights, inst.k, inst.eps) + 1e-4:
        out["over_cap"] = 1
    recomputed = numpy_cut(inst.pins, inst.offsets, inst.edge_weights, part)
    out["recomputed"] = recomputed
    if cut is None or float(cut) != recomputed:
        out["cut_mismatch"] = 1
    return out


def _incidence(n: int, pins: np.ndarray, offsets: np.ndarray):
    """Nets of every vertex, as CSR: ``nets[voff[v]:voff[v+1]]``."""
    edge_of_pin = np.repeat(np.arange(len(offsets) - 1), np.diff(offsets))
    order = np.argsort(pins, kind="stable")
    voff = np.concatenate([[0], np.cumsum(np.bincount(pins, minlength=n))])
    return edge_of_pin[order], voff


def grow_blocks(inst, seed: int) -> np.ndarray:
    """Greedy growth: each block in turn takes, from a random unassigned
    vertex, the unassigned vertex that shares the most net weight with
    it, until it holds ``ceil(W / k)``; the last block takes the rest."""
    rng = np.random.default_rng(seed)
    n, k = inst.n, inst.k
    w = np.asarray(inst.vertex_weights, np.float64)
    ew = np.asarray(inst.edge_weights, np.float64)
    nets, voff = _incidence(n, inst.pins, inst.offsets)
    target = np.ceil(w.sum() / k)
    part = np.full(n, -1, np.int64)
    for b in range(k - 1):
        score = np.zeros(n)
        free = np.nonzero(part < 0)[0]
        if len(free) == 0:
            break
        v = int(rng.choice(free))
        load = 0.0
        while True:
            part[v] = b
            load += w[v]
            if load >= target:
                break
            for e in nets[voff[v]:voff[v + 1]]:
                score[inst.pins[inst.offsets[e]:inst.offsets[e + 1]]] += ew[e]
            cand = np.where(part < 0, score, -1.0)
            v = int(np.argmax(cand))
            if cand[v] <= 0.0:
                free = np.nonzero(part < 0)[0]
                if len(free) == 0:
                    break
                v = int(rng.choice(free))
    part[part < 0] = k - 1
    return part


def fm_passes(inst, part: np.ndarray, seed: int,
              max_passes: int = 32) -> np.ndarray:
    """Passes over the vertices in random order; each vertex moves to
    the block of largest positive cut gain that keeps the cap.  Stops
    after a pass without a move."""
    rng = np.random.default_rng(seed)
    n, m, k = inst.n, inst.m, inst.k
    w = np.asarray(inst.vertex_weights, np.float64)
    ew = np.asarray(inst.edge_weights, np.float64)
    size = np.diff(inst.offsets)
    nets, voff = _incidence(n, inst.pins, inst.offsets)
    part = np.asarray(part, np.int64).copy()
    cnt = np.zeros((m, k), np.int64)
    edge_of_pin = np.repeat(np.arange(m), size)
    np.add.at(cnt, (edge_of_pin, part[inst.pins]), 1)
    bw = np.bincount(part, weights=w, minlength=k)
    cap = cap_of(w, k, inst.eps)
    for _ in range(max_passes):
        moved = 0
        for v in rng.permutation(n):
            a = part[v]
            es = nets[voff[v]:voff[v + 1]]
            if len(es) == 0:
                continue
            c = cnt[es]                                   # [deg, k]
            # leaving a: nets where v is a's last pin stop touching a;
            # nets that lay wholly in a become cut
            gain = (ew[es] * (c[:, a] == 1)) @ (c == size[es, None] - 1)
            gain = gain - ew[es][c[:, a] == size[es]].sum()
            gain[a] = 0.0
            fits = bw + w[v] <= cap + 1e-9
            fits[a] = False
            gain = np.where(fits, gain, -np.inf)
            b = int(np.argmax(gain))
            if gain[b] <= 0.0:
                continue
            part[v] = b
            cnt[es, a] -= 1
            cnt[es, b] += 1
            bw[a] -= w[v]
            bw[b] += w[v]
            moved += 1
        if moved == 0:
            break
    return part


def reference_partition(inst, seed: int = 0) -> np.ndarray:
    """The plain partitioner: greedy growth, then cut-gain passes."""
    return fm_passes(inst, grow_blocks(inst, seed), seed)


def move_gain(inst, part, seed: int = 0) -> float:
    """The share of ``part``'s cut that the reference's passes of
    single-vertex moves, started from ``part``, remove."""
    before = numpy_cut(inst.pins, inst.offsets, inst.edge_weights, part)
    after = numpy_cut(inst.pins, inst.offsets, inst.edge_weights,
                      fm_passes(inst, part, seed))
    return (before - after) / max(before, 1.0)


def random_partition(inst, seed: int = 0) -> np.ndarray:
    """A random assignment that keeps the cap: the reference with its
    refinement taken out (the control's answer)."""
    order = np.random.default_rng(seed).permutation(inst.n)
    part = np.empty(inst.n, np.int64)
    part[order] = np.arange(inst.n) % inst.k
    return part
