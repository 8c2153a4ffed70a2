"""Instance axis: batch INDEPENDENT partition requests through the one
compiled refinement engine (DESIGN.md §12).

PRs 1-5 batched the *population* (alpha) axis — one hypergraph, many
candidate solutions.  This module adds the axis above it: many
hypergraphs, each with its own population, refined together as
``[instance, alpha, n_pad]`` stacks.  ``HypergraphArrays`` already keeps
its true sizes ``n``/``m`` as traced pytree LEAVES, so stacking the
structure leaves over a leading instance axis and ``jax.vmap``-ing the
existing population implementations over it is exact: every per-lane
mask (``arange(n_pad) < n``, ghost rows, balance caps) becomes
per-instance for free.

Shape buckets.  Instances group by ``(n_pad bucket, k bucket)`` —
the same pow2 rebucketing the device coarsener uses
(``hypergraph._round_pow2`` / ``dcoarsen._rebucket_jit``) — and a group
stacks after re-padding every leaf to the group maximum.  Re-padding is
answer-preserving: padded vertices carry zero weight and are never
proposed, padded edges carry zero weight and zero pins, old ghost slots
stay inert, and acceptance ranking puts non-proposing rows after every
proposer (stable sort), so a request refined inside a bigger bucket
follows the exact trajectory of its natural-shape solo run.  The one
shape-derived *parameter* — the FM step budget ``min(n_pad, 1024)`` —
is captured per instance at stack time from the ORIGINAL arrays and
threaded through the pass as a traced scalar, so bucketing never
changes a trip count.

Per-instance k/eps.  The bucket's gain matrices are [n_pad, k_pad] with
``k_pad`` the pow2 bucket; a traced per-instance ``k_live`` masks
columns ``j >= k_live`` to NEG.  Row-major flat argmax order over the
masked matrix equals the solo [n_pad, k_live] order, so proposals, FM
move sequences and tie-breaks are bit-identical.  eps enters only
through the per-instance balance cap scalar.

Convergence.  Each instance keeps its own trip counts: under ``vmap`` a
``lax.while_loop`` lane whose cond turns False is frozen (body computed,
selected away), so an instance that converges early sits inert in the
dispatch while the others finish — exactly the semantics of running it
alone.  Within a round, already-improved alpha lanes freeze through the
``live`` mask instead of compacting out of the batch (per-lane
trajectories are invariant to which lanes share a dispatch).

Sharding (``REPRO_POP_SHARD``, same dispatcher as the population axis):
``mesh`` shards the INSTANCE axis over "pop" — every stacked leaf is
P("pop"), no collectives (instances are fully independent); ``chunk``
slices the instance axis over ``jax.local_devices()`` with async
dispatch; ``off`` is one dispatch.  All paths bit-identical per
instance (asserted by ``tests/test_service.py``).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import obs
from ..jaxcompat import shard_map
from .hypergraph import HypergraphArrays, _round_pow2
from . import metrics
from . import popshard
from . import refine as refine_mod


def k_bucket(k: int) -> int:
    """pow2 bucket for the block count (floor 2): instances with
    different k share a compiled engine at ``k_pad`` and mask with
    ``k_live``."""
    return _round_pow2(int(k), floor=2)


def bucket_n_pad(n_pad: int, grid: Optional[Sequence[int]] = None) -> int:
    """The stacking bucket for a vertex padding.  ``grid`` (the
    ``REPRO_SERVE_BUCKETS`` knob) lists allowed bucket sizes; the
    smallest grid entry >= n_pad wins, so requests of mixed sizes share
    buckets.  Without a grid (or above its top entry) the natural pow2
    padding is its own bucket."""
    if grid:
        for g in sorted(int(x) for x in grid):
            if g >= n_pad:
                return g
    return int(n_pad)


def group_key(hga: HypergraphArrays, k: int,
              grid: Optional[Sequence[int]] = None) -> Tuple[int, int]:
    """Dispatch-group key for one instance: (n_pad bucket, k bucket)."""
    return (bucket_n_pad(hga.n_pad, grid), k_bucket(k))


def _repad(h: HypergraphArrays, n_pad: int, m_pad: int, p_pad: int
           ) -> HypergraphArrays:
    """Extend a level's padding to the bucket target.  The old ghost
    vertex/edge keep zero weight in the extended arrays, so pins that
    point at them stay inert; new pad pins point at the old ghosts too.
    ``incident`` is dropped (the stacked engine is XLA-only)."""
    if (h.n_pad, h.m_pad, h.p_pad) == (n_pad, m_pad, p_pad):
        return dataclasses.replace(h, incident=None)
    ghost_v = jnp.int32(h.n_pad - 1)
    ghost_e = jnp.int32(h.m_pad - 1)
    pv = jnp.concatenate(
        [h.pin_vertex, jnp.full(p_pad - h.p_pad, ghost_v, jnp.int32)])
    pe = jnp.concatenate(
        [h.pin_edge, jnp.full(p_pad - h.p_pad, ghost_e, jnp.int32)])
    vw = jnp.concatenate(
        [h.vertex_weights, jnp.zeros(n_pad - h.n_pad, jnp.float32)])
    ew = jnp.concatenate(
        [h.edge_weights, jnp.zeros(m_pad - h.m_pad, jnp.float32)])
    es = jnp.concatenate(
        [h.edge_sizes, jnp.zeros(m_pad - h.m_pad, jnp.int32)])
    return HypergraphArrays(pin_vertex=pv, pin_edge=pe, vertex_weights=vw,
                            edge_weights=ew, edge_sizes=es, n=h.n, m=h.m,
                            incident=None)


@dataclasses.dataclass
class InstanceBatch:
    """A stacked shape bucket: structure leaves [I, ...], per-instance
    k/cap/step masks.  Never call shape properties on ``hga`` directly —
    consume it under ``jax.vmap`` (each lane sees an unbatched level)."""
    hga: HypergraphArrays        # leaves stacked over the instance axis
    k_pad: int                   # static block-count bucket
    k_live: jnp.ndarray          # [I] int32 true k per instance
    cap: jnp.ndarray             # [I] f32 per-instance balance cap
    fm_steps: jnp.ndarray        # [I] int32 solo FM budget min(n_pad,1024)
    ns: Tuple[int, ...]          # host true vertex counts
    ks: Tuple[int, ...]          # host true block counts
    orig_n_pads: Tuple[int, ...]  # natural paddings before bucketing
    # bounded migration (DESIGN.md §14): per-instance incumbent rows and
    # moved-weight budgets.  None = the whole batch is unconstrained (the
    # pre-§14 program, byte-for-byte); unconstrained instances co-batched
    # with incremental ones ride with an all-zeros incumbent and an inf
    # budget, whose masks are all-True — bit-identical trajectories.
    incumbent: Optional[jnp.ndarray] = None   # [I, n_pad] int32
    mig_budget: Optional[jnp.ndarray] = None  # [I] f32

    @property
    def n_instances(self) -> int:
        return len(self.ns)

    @property
    def n_pad(self) -> int:
        return int(self.hga.vertex_weights.shape[1])


def stack_instances(hgas: Sequence[HypergraphArrays], ks: Sequence[int],
                    epss: Sequence[float],
                    grid: Optional[Sequence[int]] = None,
                    incumbents: Optional[Sequence] = None,
                    mig_budgets: Optional[Sequence] = None) -> InstanceBatch:
    """Stack independent levels into one bucket batch.  Targets are the
    per-axis maxima over the group (``grid`` rounds the vertex axis), so
    any mix of natural pow2 paddings stacks; each instance is re-padded
    inertly first.

    ``incumbents``/``mig_budgets`` (optional, DESIGN.md §14): per-instance
    incumbent assignments and migration budgets; ``None`` entries (cold
    instances sharing the bucket) get a zeros incumbent + inf budget,
    which is bit-identical to the unconstrained trace."""
    if not (len(hgas) == len(ks) == len(epss)):
        raise ValueError("hgas/ks/epss length mismatch")
    n_pad = bucket_n_pad(max(h.n_pad for h in hgas), grid)
    m_pad = max(h.m_pad for h in hgas)
    p_pad = max(h.p_pad for h in hgas)
    k_pad = max(k_bucket(k) for k in ks)
    # caps and FM budgets come from the ORIGINAL arrays: the cap cache
    # keys on the live level object, and the step budget must match what
    # a solo run at the natural padding would use
    cap = jnp.stack([jnp.asarray(refine_mod._cap_for(h, k, eps),
                                 jnp.float32)
                     for h, k, eps in zip(hgas, ks, epss)])
    fm_steps = jnp.asarray([min(h.n_pad, 1024) for h in hgas], jnp.int32)
    rep = [_repad(h, n_pad, m_pad, p_pad) for h in hgas]
    stacked = HypergraphArrays(
        pin_vertex=jnp.stack([r.pin_vertex for r in rep]),
        pin_edge=jnp.stack([r.pin_edge for r in rep]),
        vertex_weights=jnp.stack([r.vertex_weights for r in rep]),
        edge_weights=jnp.stack([r.edge_weights for r in rep]),
        edge_sizes=jnp.stack([r.edge_sizes for r in rep]),
        n=jnp.stack([jnp.asarray(r.n, jnp.int32) for r in rep]),
        m=jnp.stack([jnp.asarray(r.m, jnp.int32) for r in rep]),
        incident=None)
    inc = mb = None
    if incumbents is not None and any(x is not None for x in incumbents):
        inc_rows = np.zeros((len(hgas), n_pad), np.int32)
        mb_rows = np.full(len(hgas), np.inf, np.float32)
        for i, x in enumerate(incumbents):
            if x is None:
                continue
            x = np.asarray(x, np.int32)
            inc_rows[i, :x.shape[0]] = x
            b = None if mig_budgets is None else mig_budgets[i]
            mb_rows[i] = np.inf if b is None else float(b)
        inc = jnp.asarray(inc_rows)
        mb = jnp.asarray(mb_rows)
    return InstanceBatch(
        hga=stacked, k_pad=k_pad,
        k_live=jnp.asarray([int(k) for k in ks], jnp.int32),
        cap=cap, fm_steps=fm_steps,
        ns=tuple(int(jnp.asarray(h.n)) if not isinstance(h.n, (int,
                 np.integer)) else int(h.n) for h in hgas),
        ks=tuple(int(k) for k in ks),
        orig_n_pads=tuple(h.n_pad for h in hgas),
        incumbent=inc, mig_budget=mb)


def stack_parts(parts_list: Sequence, n_pad: int) -> np.ndarray:
    """[A, n_i]-per-instance populations -> one [I, A, n_pad] stack."""
    rows = [np.asarray(refine_mod.pad_parts(p, n_pad), np.int32)
            for p in parts_list]
    alphas = {r.shape[0] for r in rows}
    if len(alphas) != 1:
        raise ValueError(f"instances must share alpha, got {alphas}")
    return np.stack(rows)


# --------------------------------------------------------------------------
# batched dispatch units (vmap the population impls over the instance axis)
# --------------------------------------------------------------------------
def _lp_attempt_instances_impl(hga, parts, cuts, fracs, live, attempts,
                               k: int, cap, k_live, incumbent=None,
                               mig_budget=None, pin_axis=None):
    def one(h, p, c, f, lv, att, cp, kl, inc, mb):
        return refine_mod._lp_attempt_population_impl(
            h, p, c, f, att, k, cp, live=lv, k_live=kl, incumbent=inc,
            mig_budget=mb, pin_axis=pin_axis)
    return jax.vmap(one)(hga, parts, cuts, fracs, live, attempts, cap,
                         k_live, incumbent, mig_budget)


_lp_attempt_instances = partial(jax.jit, static_argnames=("k",))(
    _lp_attempt_instances_impl)


def _hga_instance_specs(model: bool):
    """Spec (sub)tree for a STACKED HypergraphArrays: instance axis over
    "pop" on every leaf; with ``model`` (DESIGN.md §15) the [I, P_pad]
    pin tables additionally row-shard their pin axis over "model"."""
    if not model:
        return P("pop")
    return HypergraphArrays(
        pin_vertex=P("pop", "model"), pin_edge=P("pop", "model"),
        vertex_weights=P("pop"), edge_weights=P("pop"),
        edge_sizes=P("pop"), n=P("pop"), m=P("pop"), incident=None)


@lru_cache(maxsize=32)
def _lp_attempt_instances_mesh(mesh, k: int, model: bool = False):
    """Instance-axis LP attempt loop over the ("pop", "model") mesh:
    EVERY leaf — structure included — shards its instance axis over
    "pop".  Instances are independent, so there is no cross-instance
    collective; each shard runs its instances' exact solo trip counts.
    With ``model`` each instance's pin tables are additionally
    row-sharded over "model" and its pin reductions psum'd (inside the
    instance vmap — the collective is per-instance, DESIGN.md §15)."""
    def body(hga, parts, cuts, fracs, live, attempts, cap, k_live,
             incumbent, mig_budget):
        return _lp_attempt_instances_impl(
            hga, parts, cuts, fracs, live, attempts, k, cap, k_live,
            incumbent=incumbent, mig_budget=mig_budget,
            pin_axis="model" if model else None)

    fn = shard_map(body, mesh,
                   in_specs=(_hga_instance_specs(model),)
                   + (P("pop"),) * 9,
                   out_specs=(P("pop"),) * 5)
    return jax.jit(fn)


def _fm_pass_instances_impl(hga, parts, k: int, cap, steps, k_live,
                            incumbent=None, mig_budget=None,
                            pin_axis=None, assemble: str = "segsum"):
    def one(h, p, cp, st, kl, inc, mb):
        return refine_mod._fm_pass_population_impl(h, p, k, cp, st,
                                                   k_live=kl,
                                                   incumbent=inc,
                                                   mig_budget=mb,
                                                   pin_axis=pin_axis,
                                                   assemble=assemble)
    return jax.vmap(one)(hga, parts, cap, steps, k_live, incumbent,
                         mig_budget)


_fm_pass_instances = partial(jax.jit, static_argnames=("k", "assemble"))(
    _fm_pass_instances_impl)


@lru_cache(maxsize=32)
def _fm_pass_instances_mesh(mesh, k: int, model: bool = False,
                            assemble: str = "segsum"):
    def body(hga, parts, cap, steps, k_live, incumbent, mig_budget):
        return _fm_pass_instances_impl(hga, parts, k, cap, steps, k_live,
                                       incumbent=incumbent,
                                       mig_budget=mig_budget,
                                       pin_axis="model" if model
                                       else None, assemble=assemble)

    fn = shard_map(body, mesh,
                   in_specs=(_hga_instance_specs(model),)
                   + (P("pop"),) * 6,
                   out_specs=(P("pop"),) * 2)
    return jax.jit(fn)


@partial(jax.jit, static_argnames=("k",))
def _cutsize_instances(hga, parts, k: int, k_live):
    del k_live  # blocks >= k_live are empty; the k_pad sum is exact
    return jax.vmap(lambda h, ps: jax.vmap(
        lambda p: metrics.cutsize(h, p, k))(ps))(hga, parts)


def _pad_i(x, mult: int):
    """Mirror instance 0 up to a multiple of ``mult`` (the pad_rows
    pattern): mirror lanes repeat instance 0's exact computation, so
    trip counts and results are unchanged; callers slice them off."""
    r = x.shape[0] % mult
    if r == 0:
        return x
    reps = jnp.repeat(x[:1], mult - r, axis=0)
    return jnp.concatenate([x, reps], axis=0)


def _take_i(batch: InstanceBatch, idx) -> InstanceBatch:
    """Slice an instance subset out of a stacked batch (host indices)."""
    idx = np.asarray(idx)
    j = jnp.asarray(idx)
    return InstanceBatch(
        hga=jax.tree_util.tree_map(lambda x: x[j], batch.hga),
        k_pad=batch.k_pad, k_live=batch.k_live[j], cap=batch.cap[j],
        fm_steps=batch.fm_steps[j],
        ns=tuple(batch.ns[i] for i in idx),
        ks=tuple(batch.ks[i] for i in idx),
        orig_n_pads=tuple(batch.orig_n_pads[i] for i in idx),
        incumbent=None if batch.incumbent is None else batch.incumbent[j],
        mig_budget=(None if batch.mig_budget is None
                    else batch.mig_budget[j]))


# --------------------------------------------------------------------------
# host loops (per-instance trajectories == the solo population loops)
# --------------------------------------------------------------------------
def _route(shard: Optional[str]) -> str:
    return popshard.resolve(shard)


def _chunk_bounds(n: int, ndev: int) -> List[int]:
    return [n * d // ndev for d in range(ndev + 1)]


def _model_active(batch: InstanceBatch, mesh,
                  model_shard: Optional[str]) -> bool:
    """Does this stacked dispatch row-shard its pin tables over "model"?
    (``model_shard``/``REPRO_MODEL_SHARD`` routing + a real model axis
    dividing the bucket's pin padding, DESIGN.md §15)."""
    p_pad = int(batch.hga.pin_vertex.shape[-1])
    return (popshard.resolve_model(model_shard) == "mesh"
            and popshard.model_axis_active(p_pad, mesh))


def _put_hga(batch_hga, npop: int, mesh, sh, model: bool):
    """Place a stacked structure for a mesh dispatch: every leaf's
    instance axis over "pop"; with ``model`` the pin tables additionally
    shard their pin axis over "model" (DESIGN.md §15)."""
    padded = jax.tree_util.tree_map(lambda x: _pad_i(x, npop), batch_hga)
    if not model:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), padded)
    from jax.sharding import NamedSharding
    pin_sh = NamedSharding(mesh, P("pop", "model"))
    row = lambda x: jax.device_put(x, sh)
    return dataclasses.replace(
        padded,
        pin_vertex=jax.device_put(padded.pin_vertex, pin_sh),
        pin_edge=jax.device_put(padded.pin_edge, pin_sh),
        vertex_weights=row(padded.vertex_weights),
        edge_weights=row(padded.edge_weights),
        edge_sizes=row(padded.edge_sizes),
        n=row(padded.n), m=row(padded.m))


def _read(outs) -> Tuple[np.ndarray, ...]:
    """Bring a dispatch's outputs to the host: the host blocks on the
    device here (a ``wait`` span, one ``readbacks``)."""
    with obs.span("wait"):
        got = tuple(np.asarray(o) for o in outs)
    obs.count("readbacks")
    return got


def _cuts_of(batch: InstanceBatch, parts: np.ndarray) -> np.ndarray:
    """[I, A] f64 cuts of a host population stack, read back."""
    return np.asarray(_read((_cutsize_instances(
        batch.hga, jnp.asarray(parts), batch.k_pad, batch.k_live),))[0],
        np.float64)


def _dispatch_lp(batch: InstanceBatch, parts, cuts32, fracs, live, att,
                 path: str, model_shard: Optional[str] = None):
    """One grouped LP attempt dispatch; returns numpy
    (parts, cuts, improved, fracs, used) stacked [I, ...]."""
    k = batch.k_pad
    args = (jnp.asarray(parts), jnp.asarray(cuts32), jnp.asarray(fracs),
            jnp.asarray(live), jnp.asarray(att, jnp.int32))
    if path == "mesh":
        mesh = popshard.pop_mesh()
        npop = mesh.shape["pop"]
        sh = popshard.pop_sharding(mesh)
        nI = parts.shape[0]
        model = _model_active(batch, mesh, model_shard)
        put = lambda x: jax.device_put(_pad_i(x, npop), sh)
        opt = lambda x: None if x is None else put(x)
        hga_p = _put_hga(batch.hga, npop, mesh, sh, model)
        fn = _lp_attempt_instances_mesh(mesh, k, model)
        out = fn(hga_p, *(put(a) for a in args), put(batch.cap),
                 put(batch.k_live), opt(batch.incumbent),
                 opt(batch.mig_budget))
        return tuple(o[:nI] for o in _read(out))
    if path == "chunk":
        devs = popshard.local_devices()
        nI = parts.shape[0]
        ndev = min(len(devs), nI)
        if ndev > 1:
            bounds = _chunk_bounds(nI, ndev)
            outs = []
            for di in range(ndev):
                lo, hi = bounds[di], bounds[di + 1]
                put = lambda x: jax.device_put(x[lo:hi], devs[di])
                opt = lambda x: None if x is None else put(x)
                outs.append(_lp_attempt_instances(
                    jax.tree_util.tree_map(put, batch.hga),
                    *(put(a) for a in args),
                    k=k, cap=put(batch.cap), k_live=put(batch.k_live),
                    incumbent=opt(batch.incumbent),
                    mig_budget=opt(batch.mig_budget)))
            got = [_read(o) for o in outs]
            return tuple(np.concatenate([g[i] for g in got])
                         for i in range(5))
    out = _lp_attempt_instances(batch.hga, *args, k=k, cap=batch.cap,
                                k_live=batch.k_live,
                                incumbent=batch.incumbent,
                                mig_budget=batch.mig_budget)
    return _read(out)


def _fm_route(batch: InstanceBatch, path: str,
              model_shard: Optional[str] = None) -> str:
    """The FM gain route of a stacked bucket's dispatches (see
    ``refine.fm_gain_route``), sized for all of its instances."""
    model = (path == "mesh"
             and _model_active(batch, popshard.pop_mesh(), model_shard))
    return refine_mod.fm_gain_route(
        batch.n_pad, int(batch.hga.edge_weights.shape[1]),
        lanes=batch.n_instances, pin_axis="model" if model else None)


def _dispatch_fm(batch: InstanceBatch, parts, path: str,
                 model_shard: Optional[str] = None,
                 assemble: str = "segsum"):
    k = batch.k_pad
    if path == "mesh":
        mesh = popshard.pop_mesh()
        npop = mesh.shape["pop"]
        sh = popshard.pop_sharding(mesh)
        nI = parts.shape[0]
        model = _model_active(batch, mesh, model_shard)
        put = lambda x: jax.device_put(_pad_i(x, npop), sh)
        opt = lambda x: None if x is None else put(x)
        fn = _fm_pass_instances_mesh(mesh, k, model, assemble)
        out = fn(_put_hga(batch.hga, npop, mesh, sh, model),
                 put(jnp.asarray(parts)), put(batch.cap),
                 put(batch.fm_steps), put(batch.k_live),
                 opt(batch.incumbent), opt(batch.mig_budget))
        cands, cs = _read(out)
        return cands[:nI], cs[:nI].astype(np.float64)
    if path == "chunk":
        devs = popshard.local_devices()
        nI = parts.shape[0]
        ndev = min(len(devs), nI)
        if ndev > 1:
            bounds = _chunk_bounds(nI, ndev)
            outs = []
            for di in range(ndev):
                lo, hi = bounds[di], bounds[di + 1]
                put = lambda x: jax.device_put(x[lo:hi], devs[di])
                opt = lambda x: None if x is None else put(x)
                outs.append(_fm_pass_instances(
                    jax.tree_util.tree_map(put, batch.hga),
                    put(jnp.asarray(parts)), k=k, cap=put(batch.cap),
                    steps=put(batch.fm_steps), k_live=put(batch.k_live),
                    incumbent=opt(batch.incumbent),
                    mig_budget=opt(batch.mig_budget), assemble=assemble))
            got = [_read(o) for o in outs]
            return (np.concatenate([g[0] for g in got]),
                    np.concatenate([g[1] for g in got]).astype(np.float64))
    out = _fm_pass_instances(batch.hga, jnp.asarray(parts), k=k,
                             cap=batch.cap, steps=batch.fm_steps,
                             k_live=batch.k_live,
                             incumbent=batch.incumbent,
                             mig_budget=batch.mig_budget, assemble=assemble)
    cands, cs = _read(out)
    return cands, np.asarray(cs, np.float64)


def lp_refine_instances(batch: InstanceBatch, parts, max_iters: int = 24,
                        patience: int = 3, shard: Optional[str] = None,
                        model_shard: Optional[str] = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """``lp_refine_population`` for a stacked bucket: per-instance stall
    counters, per-instance attempt budgets, improved lanes frozen in
    place via the ``live`` mask.  Returns (parts [I, A, n_pad],
    cuts [I, A] f64), each instance bit-identical to its solo run."""
    path = _route(shard)
    parts = np.asarray(parts, np.int32)
    nI, alpha = parts.shape[:2]
    cuts = _cuts_of(batch, parts)
    stall = np.zeros((nI, alpha), np.int32)
    done = np.zeros((nI, alpha), bool)
    for _ in range(max_iters):
        if done.all():
            break
        active = ~done
        improved_round = np.zeros((nI, alpha), bool)
        fracs = np.ones((nI, alpha), np.float32)
        live = active.copy()
        remaining = np.full(nI, 5, np.int64)
        while True:
            act = live.any(axis=1) & (remaining > 0)
            if not act.any():
                break
            att = np.where(act, np.maximum(remaining, 0), 0)
            new_parts, new_cuts, improved, new_fracs, used = _dispatch_lp(
                batch, parts, cuts.astype(np.float32), fracs, live, att,
                path, model_shard)
            parts = np.where(live[:, :, None], new_parts, parts)
            cuts = np.where(live, new_cuts.astype(np.float64), cuts)
            fracs = np.where(live, new_fracs, fracs)
            improved = improved.astype(bool) & live
            obs.count("lp.lane_attempts", live, used[:, None])
            obs.count("lp.lane_improved", improved)
            improved_round |= improved
            remaining = remaining - np.asarray(used, np.int64)
            live = live & ~improved
        stall = np.where(active,
                         np.where(improved_round, 0, stall + 1), stall)
        done |= stall >= patience
    return parts, cuts


def fm_refine_instances(batch: InstanceBatch, parts,
                        max_passes: int = 8, shard: Optional[str] = None,
                        model_shard: Optional[str] = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """``fm_refine_population`` for a stacked bucket.  Converged lanes
    are re-dispatched but inert (an unimproving FM pass repeats its
    rejected candidate deterministically), so per-lane acceptance
    decisions match the compacting solo loop exactly."""
    path = _route(shard)
    assemble = _fm_route(batch, path, model_shard)
    parts = np.asarray(parts, np.int32)
    nI, alpha = parts.shape[:2]
    cuts = _cuts_of(batch, parts)
    done = np.zeros((nI, alpha), bool)
    for _ in range(max_passes):
        if done.all():
            break
        cands, cs = _dispatch_fm(batch, parts, path, model_shard, assemble)
        pending = ~done
        take = (cs < cuts - 1e-6) & pending
        obs.count("fm.lane_passes", pending)
        if assemble == "dense":
            obs.count("fm.dense_lanes", pending)
        obs.count("fm.lane_accepted", take)
        parts = np.where(take[:, :, None], cands, parts)
        cuts = np.where(take, cs, cuts)
        done |= ~take
    return parts, cuts


def refine_instances(batch: InstanceBatch, parts,
                     fm_node_limit: int = 4096, max_iters: int = 24,
                     patience: int = 3, shard: Optional[str] = None,
                     model_shard: Optional[str] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Two-tier refinement for a stacked bucket, the instance-axis
    mirror of ``refine.refine_population``: the LP tier covers every
    instance; the FM tier runs on the sub-batch of instances whose true
    n is within ``fm_node_limit`` (sliced out and written back), exactly
    the per-instance decision the solo driver makes."""
    with obs.span("refine.lp"):
        parts, cuts = lp_refine_instances(batch, parts, max_iters=max_iters,
                                          patience=patience, shard=shard,
                                          model_shard=model_shard)
    fm_idx = [i for i, n in enumerate(batch.ns) if n <= fm_node_limit]
    if fm_idx:
        with obs.span("refine.fm"):
            if len(fm_idx) == batch.n_instances:
                parts, cuts = fm_refine_instances(batch, parts, shard=shard,
                                                  model_shard=model_shard)
            else:
                sub = _take_i(batch, fm_idx)
                sp, sc = fm_refine_instances(sub, parts[fm_idx],
                                             shard=shard,
                                             model_shard=model_shard)
                parts[fm_idx] = sp
                cuts[fm_idx] = sc
    return parts, cuts


def refine_grouped(entries, grid: Optional[Sequence[int]] = None,
                   fm_node_limit: int = 4096, max_iters: int = 24,
                   patience: int = 3, shard: Optional[str] = None,
                   model_shard: Optional[str] = None
                   ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Refine a heterogeneous set of instances by bucketed stacks.

    ``entries``: sequence of ``(hga, parts [A, n_pad_i], k, eps)`` or
    ``(hga, parts, k, eps, incumbent, mig_budget)`` — incremental
    entries (DESIGN.md §14) carry their incumbent assignment [n_i] and
    moved-weight budget; both entry kinds co-batch in one bucket (cold
    entries ride the constrained trace with an inf budget, which is
    bit-identical to the unconstrained one).
    Returns per-entry ``(parts [A, n_pad_i], cuts [A])`` in input order,
    each bit-identical to ``refine.refine_population`` on that entry
    alone (with the same incumbent/budget).  This is the dispatch unit
    the V-cycle drivers and the partition service share.
    """
    groups: dict = {}
    for i, e in enumerate(entries):
        groups.setdefault(group_key(e[0], e[2], grid), []).append(i)
    out: List = [None] * len(entries)
    for (n_bucket, k_bucket_), idx in groups.items():
        hgas = [entries[i][0] for i in idx]
        ks = [entries[i][2] for i in idx]
        epss = [entries[i][3] for i in idx]
        incs = [entries[i][4] if len(entries[i]) > 4 else None
                for i in idx]
        mbs = [entries[i][5] if len(entries[i]) > 5 else None
               for i in idx]
        if all(x is None for x in incs):
            incs = mbs = None
        with obs.span("refine", n=n_bucket, k=k_bucket_):
            with obs.span("refine.stack"):
                batch = stack_instances(hgas, ks, epss, grid=grid,
                                        incumbents=incs, mig_budgets=mbs)
                parts = stack_parts([entries[i][1] for i in idx],
                                    batch.n_pad)
            rp, rc = refine_instances(batch, parts,
                                      fm_node_limit=fm_node_limit,
                                      max_iters=max_iters,
                                      patience=patience, shard=shard,
                                      model_shard=model_shard)
        for j, i in enumerate(idx):
            out[i] = (rp[j][:, : batch.orig_n_pads[j]], rc[j])
    return out
