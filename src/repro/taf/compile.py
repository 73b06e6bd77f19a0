"""Whole-plan compilation: one jitted device dispatch per temporal query.

The staged ``PlanExecutor`` crosses the host/device boundary per stage; a
T-point temporal query pays a Python loop (or at best one numpy pass)
per operator.  This module lowers the terminal stage of a validated Plan
— ``Slice`` / ``Compute`` / ``Evolution`` — into ONE jitted JAX program
over the batched-replay arrays:

* ``Slice([t1..tT])``                — the device ``state_at_many``: per-
  node presence/attrs at every timepoint, bit-identical to the host
  replay engine (attrs by searchsorted + cumulative last-write index
  per row of ``SoN.padded_events()``);
* ``Compute(style="temporal", fn=<FusedOp>)`` — the temporal-analytics
  kernel family (``pagerank``/``components``/``triangles``) over
  ``EdgeReplay``'s pair table, exported once per operand via
  ``EdgeReplay.device_export()`` as a table of each pair's existence
  changes and kept device-resident;
* ``Evolution(fn=<FusedScalarOp>)``  — the same per-node programs with a
  per-timepoint reduction folded into the jit.

Presence and existence both come from tables of state changes
(``replay.change_table``): a node's presence changes
(``replay.node_presence_changes``) and a pair's existence changes.  The
state at t is the row's base state XOR the parity of its changes at or
before t — a compare and a parity reduction per row, with no search.

Programs are cached keyed on plan *shape* — stage kind, op identity and
static params, operand array shapes/dtypes, and T — so repeated queries
re-trace zero times (``STATS["traces"]`` counts actual traces; tests and
the ``fusion`` bench assert cache hits).  Uncovered plan shapes fall
back transparently to the staged executor; ``PlanResult.notes`` records
which path ran and why.

Every ``FusedOp`` carries a numpy ``host`` implementation with identical
semantics — it IS the staged path for the same plan (the op is a
vectorized temporal compute fn), which is what the randomized parity
tests compare against: bit-identical for the integer-valued ops
(components, triangles, slice), float32-vs-float64 tolerance for
PageRank (documented in docs/api.md).

Aggregate runs as a host epilogue over the device series (the staged
``_aggregate`` code verbatim), keeping aggregated results bit-identical
between paths; the T-point temporal body is the single device dispatch.

jax imports are deferred into the lowering path so plans that fall back
never pay them.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro import trace
from repro.core.events import NATTR_SET, NODE_DEL
from repro.taf import operators as ops
from repro.taf import replay
from repro.taf.son import SoN, SoTS

# sentinel distinguishing "not covered -> run staged" from a fused value
MISS = object()

# fuse a terminal Slice only past this many timepoints: below it the host
# numpy replay wins and the executor's replay LRU already dedups repeats
MIN_FUSE_T = 16

# dense-adjacency budget (elements) for the triangle program: T*N^2 above
# this falls back to the staged path rather than materializing the stack
# (the tiled motif kernel itself compiles at any N)
DENSE_BUDGET = 64_000_000

ENABLED = True

STATS: Dict[str, int] = {
    "traces": 0,           # actual jit traces (cache misses that compiled)
    "compile_hits": 0,     # program served from the compile cache
    "compile_misses": 0,
    "fused_runs": 0,
    "fallback_runs": 0,
    "operand_uploads": 0,  # device-resident operand exports built
    "flip_events": 0,      # pair events seen by the edge-operand exports
    "flip_changes": 0,     # of them, existence changes the exports keep
    "node_events": 0,      # presence changes in the node-operand exports' tables
    "node_slots": 0,       # their slots, N x C each
    "node_events_seen": 0,  # every event of the rows those exports read
    "node_deletes": 0,     # NODE_DEL events among the events read
}

_PROGRAM_CACHE_MAX = 64
_programs: "OrderedDict[Tuple, Any]" = OrderedDict()

# device-resident operand arrays, keyed (operand_key(son), flavor) and
# weakref-guarded against id() recycling like the executor's ReplayCache
_operands = replay.ReplayCache(maxsize=16)


def clear_cache() -> None:
    _programs.clear()
    _operands.clear()


def cache_stats() -> Dict[str, int]:
    return dict(STATS, programs=len(_programs), operands=len(_operands))


@contextlib.contextmanager
def disabled():
    """Force the staged executor path (tests / staged-vs-fused benches)."""
    global ENABLED
    prev, ENABLED = ENABLED, False
    try:
        yield
    finally:
        ENABLED = prev


# ---------------------------------------------------------------------------
# Fused ops: host semantics + device lowering under one object
# ---------------------------------------------------------------------------


def _host_edges(sots: SoTS, ts, present) -> Tuple[np.ndarray, np.ndarray,
                                                  np.ndarray]:
    """Canonical undirected edge list + per-timepoint liveness (host).

    Edges join member *rows* (non-member neighbors drop out, self-loops
    drop out); the two directed pair rows of one undirected edge are
    OR-folded.  An edge is live at t iff its pair exists and BOTH
    endpoints are present.  The device programs implement the identical
    semantics from ``EdgeReplay.device_export()``.
    """
    N, T = present.shape
    er = replay.edge_replay(sots)
    exist = er.exist_matrix(ts)  # (P, T)
    v = replay.member_rows(er.pair_other, sots.node_ids)
    u = er.pair_center.astype(np.int64)
    valid = (v >= 0) & (u != v)
    cu = np.minimum(u[valid], v[valid].astype(np.int64))
    cv = np.maximum(u[valid], v[valid].astype(np.int64))
    key = cu * max(N, 1) + cv
    uniq, inv = (np.unique(key, return_inverse=True) if len(key)
                 else (np.empty(0, np.int64), np.empty(0, np.int64)))
    live = np.zeros((len(uniq), T), bool)
    if len(uniq):
        np.logical_or.at(live, inv, exist[valid] == 1)
    eu = (uniq // max(N, 1)).astype(np.int64)
    ev = (uniq % max(N, 1)).astype(np.int64)
    live &= (present[eu] == 1) & (present[ev] == 1)
    return eu, ev, live


class FusedOp:
    """A temporal-analytics op the plan compiler can lower.

    Doubles as a vectorized temporal compute fn: the staged executor
    calls ``__call__(present, attrs, son, t)`` (numpy, the reference
    semantics); the compiler recognizes the instance and runs
    ``device()`` inside one jitted program instead.
    """

    vectorized = True
    name = "fused"

    def params(self) -> Tuple:
        return ()

    def __call__(self, present, attrs, son, t, **kw):
        ts = np.atleast_1d(np.asarray(t, np.int64))
        present = np.asarray(present).reshape(len(son), len(ts))
        return self.host(son, ts, present)

    def host(self, sots: SoTS, ts, present) -> np.ndarray:
        raise NotImplementedError

    def device(self, jnp_mod, arrs, act, live):
        """(N, T) series from device arrays: ``act (T, N)`` f32 presence,
        ``live (T, E)`` f32 edge liveness, ``arrs`` the operand export."""
        raise NotImplementedError


class PageRankOp(FusedOp):
    """Temporal PageRank: damped power iteration (fixed ``iters``,
    uniform dangling-mass redistribution, inactive nodes pinned to 0)
    per timepoint.  Host math runs in float64, the device program in
    float32 — parity within documented tolerance."""

    name = "pagerank"

    def __init__(self, damping: float = 0.85, iters: int = 20):
        self.damping = float(damping)
        self.iters = int(iters)

    def params(self):
        return (self.damping, self.iters)

    def host(self, sots, ts, present):
        u, v, live = _host_edges(sots, ts, present)
        N, T = present.shape
        out = np.zeros((N, T))
        for j in range(T):
            m = live[:, j]
            uj, vj = u[m], v[m]
            act = (present[:, j] == 1).astype(np.float64)
            n = max(act.sum(), 1.0)
            deg = np.zeros(N)
            np.add.at(deg, uj, 1.0)
            np.add.at(deg, vj, 1.0)
            r = act / n
            dmask = act * (deg == 0)
            for _ in range(self.iters):
                contrib = np.where(deg > 0, r / np.maximum(deg, 1.0), 0.0)
                nxt = np.zeros(N)
                np.add.at(nxt, vj, contrib[uj])
                np.add.at(nxt, uj, contrib[vj])
                dangling = float((r * dmask).sum())
                r = act * ((1.0 - self.damping) / n
                           + self.damping * (nxt + dangling / n))
            out[:, j] = r
        return out

    def device(self, jnp, arrs, act, live):
        frow, fcol, feid = arrs["frow"], arrs["fcol"], arrs["feid"]
        live2 = live[feid]  # (2E, T) contiguous rows
        deg = jnp.zeros(act.shape, jnp.float32).at[frow].add(
            live2, indices_are_sorted=True, mode="drop")
        n = jnp.maximum(jnp.sum(act, axis=0, keepdims=True), 1.0)
        r = act / n
        dmask = act * (deg == 0).astype(jnp.float32)
        for _ in range(self.iters):
            contrib = jnp.where(deg > 0, r / jnp.maximum(deg, 1.0), 0.0)
            nxt = jnp.zeros(act.shape, jnp.float32).at[frow].add(
                contrib[fcol] * live2, indices_are_sorted=True, mode="drop")
            dangling = jnp.sum(r * dmask, axis=0, keepdims=True)
            r = act * ((1.0 - self.damping) / n
                       + self.damping * (nxt + dangling / n))
        return r  # (N, T) f32


class ComponentsOp(FusedOp):
    """Temporal connected components: bounded min-label propagation
    (``iters`` rounds; exact for components of diameter <= iters).
    Labels are min member-row indices, -1 on absent nodes — integer, so
    host and device are bit-identical."""

    name = "components"

    def __init__(self, iters: int = 32):
        self.iters = int(iters)

    def params(self):
        return (self.iters,)

    def host(self, sots, ts, present):
        u, v, live = _host_edges(sots, ts, present)
        N, T = present.shape
        act = present == 1
        labels = np.where(act, np.arange(N, dtype=np.int64)[:, None], N)
        for _ in range(self.iters):
            lu = np.where(live, labels[u], N)
            lv = np.where(live, labels[v], N)
            new = labels.copy()
            if len(u):
                np.minimum.at(new, u, lv)
                np.minimum.at(new, v, lu)
            labels = new
        return np.where(act, labels, -1).astype(np.float64)

    def device(self, jnp, arrs, act, live):
        import jax

        frow, fcol, feid = arrs["frow"], arrs["fcol"], arrs["feid"]
        N, T = act.shape
        on = act > 0
        iota = jax.lax.broadcasted_iota(jnp.int32, (N, T), 0)
        labels = jnp.where(on, iota, N)
        alive = live[feid] > 0  # (2E, T)
        for _ in range(self.iters):
            msgs = jnp.where(alive, labels[fcol], N)
            labels = labels.at[frow].min(
                msgs, indices_are_sorted=True, mode="drop")
        return jnp.where(on, labels, -1)  # (N, T) int32


class TrianglesOp(FusedOp):
    """Temporal triangle participation per node (diag(A^3)/2), over the
    packed pair table's live edges.  Integer counts — host and device
    are bit-identical (f32 accumulation is exact below 2^24)."""

    name = "triangles"

    def host(self, sots, ts, present):
        u, v, live = _host_edges(sots, ts, present)
        N, T = present.shape
        out = np.zeros((N, T))
        for j in range(T):
            m = live[:, j]
            a = np.zeros((N, N), np.float32)
            a[u[m], v[m]] = 1.0
            a[v[m], u[m]] = 1.0
            a2 = a @ a
            out[:, j] = np.round((a2 * a).sum(0) * 0.5)
        return out

    def device(self, jnp, arrs, act, live):
        from repro.kernels.temporal_motif import ops as motif_ops

        u, v = arrs["edge_u"], arrs["edge_v"]
        N, T = act.shape
        live_t = live.T.astype(jnp.bfloat16)  # (T, E), 0/1 exact
        adj = (jnp.zeros((T, N, N), jnp.bfloat16)
               .at[:, u, v].max(live_t).at[:, v, u].max(live_t))
        return motif_ops.temporal_motif(adj).T  # (N, T) int32


class FusedScalarOp:
    """Evolution-stage wrapper: a FusedOp's per-node series reduced to a
    scalar per timepoint, on both paths.  Usable directly as a
    vectorized evolution fn (the staged host path)."""

    vectorized = True

    def __init__(self, base: FusedOp, reduce: str):
        self.base = base
        self.reduce = reduce
        self.name = f"{base.name}.{reduce}"

    def params(self):
        return (self.reduce,) + tuple(self.base.params())

    def __call__(self, son, ts):
        ts = np.asarray(ts, np.int64).ravel()
        present, _ = replay.state_at_many(son, ts)
        series = self.base.host(son, ts, present)
        return self._reduce_host(series, present)

    def _reduce_host(self, series, present):
        N, T = series.shape
        if self.reduce == "sum3":  # per-node triangle counts -> totals
            return series.sum(axis=0) / 3.0
        if self.reduce == "count_components":
            own = series == np.arange(N, dtype=np.float64)[:, None]
            return (own & (present == 1)).sum(axis=0).astype(np.float64)
        if self.reduce == "max":
            return series.max(axis=0, initial=0.0)
        raise ValueError(self.reduce)

    def reduce_device(self, jnp, series_nt, act):
        """(T,) device reduction; integer reducers stay exact and finish
        their float math on the host (``epilogue``)."""
        if self.reduce == "sum3":
            return jnp.sum(series_nt.astype(jnp.int32), axis=0)
        if self.reduce == "count_components":
            import jax

            N, T = act.shape
            iota = jax.lax.broadcasted_iota(jnp.int32, (N, T), 0)
            own = (series_nt == iota) & (act > 0)
            return jnp.sum(own.astype(jnp.int32), axis=0)
        if self.reduce == "max":
            return jnp.max(series_nt, axis=0, initial=0.0)
        raise ValueError(self.reduce)

    def epilogue(self, reduced: np.ndarray) -> np.ndarray:
        if self.reduce == "sum3":
            return reduced.astype(np.float64) / 3.0
        return reduced.astype(np.float64)


def pagerank(damping: float = 0.85, iters: int = 20) -> PageRankOp:
    return PageRankOp(damping=damping, iters=iters)


def components(iters: int = 32) -> ComponentsOp:
    return ComponentsOp(iters=iters)


def triangles() -> TrianglesOp:
    return TrianglesOp()


def triangle_count() -> FusedScalarOp:
    """Evolution fn: total triangles per timepoint."""
    return FusedScalarOp(TrianglesOp(), "sum3")


def component_count(iters: int = 32) -> FusedScalarOp:
    """Evolution fn: number of connected components per timepoint."""
    return FusedScalarOp(ComponentsOp(iters=iters), "count_components")


def max_pagerank(damping: float = 0.85, iters: int = 20) -> FusedScalarOp:
    """Evolution fn: the top PageRank score per timepoint."""
    return FusedScalarOp(PageRankOp(damping=damping, iters=iters), "max")


# ---------------------------------------------------------------------------
# Device operand export (uploaded once per operand, weakref-guarded)
# ---------------------------------------------------------------------------


def _node_arrays(son: SoN):
    """The node operand's device arrays: each node's presence changes
    (``replay.node_presence_changes``) and its presence at t0."""
    key = (replay.operand_key(son), "node")
    hit = _operands.get(key, owner=son)
    if hit is not None:
        return hit
    import jax.numpy as jnp

    STATS["operand_uploads"] += 1
    with trace.span("compile.export_node"):
        exp = replay.node_presence_changes(son)
        STATS["node_events"] += exp["n_changes"]
        STATS["node_slots"] += exp["pres_t"].size
        STATS["node_events_seen"] += exp["n_events"]
        STATS["node_deletes"] += exp["n_deletes"]
        arrs = {
            "pres_t": jnp.asarray(_device_times(exp["pres_t"])),
            "init_present": jnp.asarray(exp["base"].astype(np.int32)),
        }
    _operands.put(key, arrs, owner=son)
    return arrs


def _slice_arrays(son: SoN):
    """The fused slice's operand: the node operand plus the padded
    (N, Emax) table of every event that ``_dev_attrs`` searches."""
    key = (replay.operand_key(son), "slice")
    hit = _operands.get(key, owner=son)
    if hit is not None:
        return hit
    import jax.numpy as jnp

    node = _node_arrays(son)
    STATS["operand_uploads"] += 1
    with trace.span("compile.export_node"):
        pads = son.padded_events()
        arrs = dict(
            node,
            ev_t=jnp.asarray(pads["t"]),
            ev_kind=jnp.asarray(pads["kind"].astype(np.int32)),
            ev_key=jnp.asarray(pads["key"].astype(np.int32)),
            ev_val=jnp.asarray(pads["val"]),
            init_attrs=jnp.asarray(son.init_attrs),
        )
    _operands.put(key, arrs, owner=son)
    return arrs


def _edge_arrays(sots: SoTS):
    key = (replay.operand_key(sots), "edge")
    hit = _operands.get(key, owner=sots)
    if hit is not None:
        return hit
    STATS["operand_uploads"] += 1
    with trace.span("compile.export_edge"):
        arrs = _export_edge(sots)
    _operands.put(key, arrs, owner=sots)
    return arrs


def _export_edge(sots: SoTS):
    """The edge operand's device arrays, from the pair table of existence
    changes (``EdgeReplay.device_export``) and the canonical edges."""
    import jax.numpy as jnp

    N = len(sots)
    er = replay.edge_replay(sots)
    exp = er.device_export()
    STATS["flip_events"] += exp["n_events"]
    STATS["flip_changes"] += exp["n_changes"]
    chg_t, base = exp["chg_t"], exp["base"]
    if er.n_pairs == 0:  # dummy never-existing pair keeps gathers in-bounds
        chg_t = np.full((1, 1), np.iinfo(np.int64).max, np.int64)
        base = np.zeros(1, np.int8)
    v = replay.member_rows(exp["pair_other"], sots.node_ids).astype(np.int64)
    u = exp["pair_center"].astype(np.int64)
    valid = (v >= 0) & (u != v)
    cu = np.minimum(u[valid], v[valid])
    cv = np.maximum(u[valid], v[valid])
    ekey = cu * max(N, 1) + cv
    uniq = np.unique(ekey) if len(ekey) else np.empty(0, np.int64)
    E = max(len(uniq), 1)
    eu = np.zeros(E, np.int32)
    ev_ = np.zeros(E, np.int32)
    eu[: len(uniq)] = uniq // max(N, 1)
    ev_[: len(uniq)] = uniq % max(N, 1)
    # the <=2 directed pair rows per canonical edge (OR-folded by gather,
    # not scatter: contiguous T-rows are cheap, scatters are not)
    pair_a = np.zeros(E, np.int32)
    pair_b = np.zeros(E, np.int32)
    edge_valid = np.zeros(E, np.float32)
    if len(uniq):
        rows = np.nonzero(valid)[0]
        order = np.argsort(ekey, kind="stable")
        srt_keys, srt_rows = ekey[order], rows[order]
        first = np.searchsorted(srt_keys, uniq, side="left")
        last = np.searchsorted(srt_keys, uniq, side="right") - 1
        pair_a[: len(uniq)] = srt_rows[first]
        pair_b[: len(uniq)] = srt_rows[last]
        edge_valid[: len(uniq)] = 1.0
    # flat incidence (2E,) sorted by node: one contiguous-row scatter per
    # propagation step instead of two scalar-indexed ones
    frow = np.concatenate([eu, ev_]).astype(np.int64)
    fcol = np.concatenate([ev_, eu])
    feid = np.concatenate([np.arange(E), np.arange(E)]).astype(np.int32)
    o = np.argsort(frow, kind="stable")
    arrs = {
        "chg_t": jnp.asarray(_device_times(chg_t)),
        "base": jnp.asarray(base.astype(np.int32)),
        "edge_u": jnp.asarray(eu),
        "edge_v": jnp.asarray(ev_),
        "pair_a": jnp.asarray(pair_a),
        "pair_b": jnp.asarray(pair_b),
        "edge_valid": jnp.asarray(edge_valid),
        "frow": jnp.asarray(frow[o].astype(np.int32)),
        "fcol": jnp.asarray(fcol[o]),
        "feid": jnp.asarray(feid[o]),
        "n_real_edges": len(uniq),
    }
    return arrs


def _device_times(t: np.ndarray) -> np.ndarray:
    """A change table's times with its int64-max pads re-sentinelled in
    the device's integer dtype (they would wrap under jax's default
    int32, as in ``degree_series_kernel``)."""
    import jax

    big = np.iinfo(jax.dtypes.canonicalize_dtype(np.int64)).max
    return np.where(t == np.iinfo(np.int64).max, big, t)


# ---------------------------------------------------------------------------
# Device programs (jnp; shared by every covered plan shape)
# ---------------------------------------------------------------------------


def _dev_parity(jnp, chg_t, base, tsv):
    """(R, T) int32 state of R rows from the padded table of their state
    changes (``replay.change_table``; pads at the dtype's max): changes
    alternate, so a row's state at t is ``base XOR (number of changes
    <= t) mod 2`` — the compare XOR-reduced over the C columns, which
    fuses into its consumers at any C, with no search and no gather."""
    import jax

    odd = jax.lax.reduce(chg_t[:, :, None] <= tsv[None, None, :],
                         np.bool_(False), jax.lax.bitwise_xor, (1,))
    return base[:, None] ^ odd.astype(jnp.int32)


def _dev_presence(jnp, node, tsv):
    """(N, T) int32 presence — the device ``state_at_many`` presence
    half, from each node's presence changes."""
    return _dev_parity(jnp, node["pres_t"], node["init_present"], tsv)


def _dev_attrs(jnp, node, tsv, cnt_cache=None):
    """(N, T, K) int32 attrs — last write per (node, key) with NODE_DEL
    clearing every key, exactly the host replay semantics."""
    import jax

    ev_t, kind = node["ev_t"], node["ev_kind"]
    ekey, eval_ = node["ev_key"], node["ev_val"]
    big = jnp.iinfo(ev_t.dtype).max
    ev_t_s = jnp.where(kind < 0, big, ev_t)
    cnt = jax.vmap(lambda row: jnp.searchsorted(row, tsv, side="right"))(ev_t_s)
    E = ev_t.shape[1]
    K = node["init_attrs"].shape[1]
    rank = jnp.broadcast_to(jnp.arange(E, dtype=jnp.int32)[None, :],
                            ev_t.shape)
    cols = []
    for k in range(K):  # K is small and static
        wmask = ((kind == NATTR_SET) & (ekey == k)) | (kind == NODE_DEL)
        wlast = jax.lax.cummax(jnp.where(wmask, rank, -1), axis=1)
        widx = jnp.take_along_axis(wlast, jnp.maximum(cnt - 1, 0), axis=1)
        widx = jnp.where(cnt > 0, widx, -1)
        kind_at = jnp.take_along_axis(kind, jnp.maximum(widx, 0), axis=1)
        val_at = jnp.take_along_axis(eval_, jnp.maximum(widx, 0), axis=1)
        col = jnp.where(widx >= 0,
                        jnp.where(kind_at == NODE_DEL, -1, val_at),
                        node["init_attrs"][:, k][:, None])
        cols.append(col)
    return jnp.stack(cols, axis=-1)


def _dev_edge_live(jnp, edge, act, tsv):
    """(E, T) f32 edge liveness from the padded table of existence
    changes (``_dev_parity``).  The <=2 directed pair rows are OR-folded
    by contiguous-row gather and masked by both endpoints' presence.
    ``act`` is (N, T) f32 — everything stays (entity, T)-major so
    propagation scatters move whole contiguous T-rows."""
    pair_live = _dev_parity(jnp, edge["chg_t"], edge["base"],
                            tsv).astype(jnp.float32)  # (P, T)
    el = jnp.maximum(pair_live[edge["pair_a"]], pair_live[edge["pair_b"]])
    el = el * edge["edge_valid"][:, None]
    return el * act[edge["edge_u"]] * act[edge["edge_v"]]


# ---------------------------------------------------------------------------
# Program cache + lowering
# ---------------------------------------------------------------------------


def _shape_sig(arrs) -> Tuple:
    return tuple(sorted(
        (k, tuple(v.shape), str(v.dtype))
        for k, v in arrs.items() if hasattr(v, "shape")))


def _get_program(key, builder):
    prog = _programs.get(key)
    if prog is None:
        STATS["compile_misses"] += 1
        prog = builder()
        _programs[key] = prog
        while len(_programs) > _PROGRAM_CACHE_MAX:
            _programs.popitem(last=False)
    else:
        STATS["compile_hits"] += 1
        _programs.move_to_end(key)
    return prog


def _build_slice_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def prog(node, tsv):
        STATS["traces"] += 1  # runs at trace time only
        return _dev_presence(jnp, node, tsv), _dev_attrs(jnp, node, tsv)

    return prog


def _build_series_program(op: FusedOp):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def prog(node, edge, tsv):
        STATS["traces"] += 1
        act = _dev_presence(jnp, node, tsv).astype(jnp.float32)  # (N, T)
        live = _dev_edge_live(jnp, edge, act, tsv)
        return op.device(jnp, edge, act, live)

    return prog


def _build_evolution_program(sop: FusedScalarOp):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def prog(node, edge, tsv):
        STATS["traces"] += 1
        act = _dev_presence(jnp, node, tsv).astype(jnp.float32)
        live = _dev_edge_live(jnp, edge, act, tsv)
        series = sop.base.device(jnp, edge, act, live)
        return sop.reduce_device(jnp, series, act)

    return prog


def _tsv(ts) -> "Any":
    import jax.numpy as jnp

    return jnp.asarray(np.asarray(ts, np.int64))


# ---------------------------------------------------------------------------
# Entry point (called by PlanExecutor for every terminal stage)
# ---------------------------------------------------------------------------


def try_fused(operand, stage, replay_cache=None):
    """Run one terminal stage fused if its shape is covered.

    Returns ``(value, notes)``; ``value is MISS`` means "not covered,
    run the staged path" with notes carrying the reason.
    """
    if not ENABLED:
        return MISS, ("compile: staged (fusion disabled)",)
    if operand is None or len(operand) == 0:
        return MISS, ("compile: staged (empty operand)",)
    k = stage.kind
    try:
        if k == "slice":
            return _fused_slice(operand, stage, replay_cache)
        if k == "compute":
            if stage.style == "temporal" and isinstance(stage.fn, FusedOp):
                return _fused_compute(operand, stage)
            return MISS, (f"compile: staged compute (style={stage.style!r}, "
                          "fn is not a FusedOp)",)
        if k == "evolution":
            if isinstance(stage.fn, FusedScalarOp):
                return _fused_evolution(operand, stage)
            return MISS, ("compile: staged evolution (fn is not a "
                          "FusedScalarOp)",)
    except ImportError as e:  # pragma: no cover - jax missing
        return MISS, (f"compile: staged (device backend unavailable: {e})",)
    return MISS, (f"compile: staged ({k})",)


def _fused_slice(operand, stage, replay_cache):
    if np.isscalar(stage.ts):
        return MISS, ("compile: staged slice (scalar timepoint)",)
    ts = np.asarray(list(stage.ts), np.int64).ravel()
    T = len(ts)
    if T < MIN_FUSE_T:
        return MISS, (f"compile: staged slice (T={T} < MIN_FUSE_T="
                      f"{MIN_FUSE_T})",)
    # share the executor's replay LRU: a repeated fused slice re-dispatches
    # nothing, and a fused slice never poisons the staged cache (values are
    # bit-identical by construction)
    ckey = (replay.operand_key(operand),
            ("multi", tuple(int(x) for x in ts)))
    if replay_cache is not None:
        hit = replay_cache.get(ckey, owner=operand)
        if hit is not None:
            value = {kk: (vv.copy() if isinstance(vv, np.ndarray) else vv)
                     for kk, vv in hit.items()}
            return value, ("compile: fused slice (replay-LRU hit)",)
    with trace.span("compile.dispatch"):
        node = _slice_arrays(operand)
        key = ("slice", _shape_sig(node), T)
        hit_before = key in _programs
        prog = _get_program(key, _build_slice_program)
        pres, attrs = prog(node, _tsv(ts))
    with trace.span("compile.readback"):
        value = {
            "present": np.asarray(pres).astype(operand.init_present.dtype),
            "attrs": np.asarray(attrs).astype(operand.init_attrs.dtype),
            "t": ts,
        }
    if replay_cache is not None:
        replay_cache.put(ckey, value, owner=operand)
        value = {kk: (vv.copy() if isinstance(vv, np.ndarray) else vv)
                 for kk, vv in value.items()}
    STATS["fused_runs"] += 1
    note = (f"compile: fused slice (T={T}, "
            f"{'cache hit' if hit_before else 'traced'})")
    return value, (note,)


def _check_sots(operand):
    if not isinstance(operand, SoTS):
        raise ValueError(
            "fused temporal-analytics ops need a SoTS operand (adjacency); "
            "fetch with subgraphs()/build_sots")


def _fused_compute(operand, stage):
    _check_sots(operand)
    op: FusedOp = stage.fn
    ts = ops.eval_points(operand, stage.points).astype(np.int64)
    T = len(ts)
    miss = _budget_miss(op, operand, T)
    if miss is not None:
        return miss
    with trace.span("compile.dispatch"):
        node = _node_arrays(operand)
        edge = _edge_arrays(operand)
        key = ("compute", op.name, op.params(), _shape_sig(node),
               _shape_sig(edge), T)
        hit_before = key in _programs
        prog = _get_program(key, lambda: _build_series_program(op))
        series = prog(node, edge, _tsv(ts))
    with trace.span("compile.readback"):
        out = np.asarray(series, np.float64).reshape(len(operand), T)
    STATS["fused_runs"] += 1
    note = (f"compile: fused compute[{op.name}] (T={T}, "
            f"{'cache hit' if hit_before else 'traced'})")
    return (ts, out), (note,)


def _fused_evolution(operand, stage):
    _check_sots(operand)
    sop: FusedScalarOp = stage.fn
    if stage.points is None:
        ts = np.linspace(operand.t0, operand.t1,
                         stage.n_samples).astype(np.int64)
    else:
        ts = ops.eval_points(operand, stage.points).astype(np.int64)
    T = len(ts)
    miss = _budget_miss(sop.base, operand, T)
    if miss is not None:
        return miss
    with trace.span("compile.dispatch"):
        node = _node_arrays(operand)
        edge = _edge_arrays(operand)
        key = ("evolution", sop.name, sop.params(), _shape_sig(node),
               _shape_sig(edge), T)
        hit_before = key in _programs
        prog = _get_program(key, lambda: _build_evolution_program(sop))
        reduced = prog(node, edge, _tsv(ts))
    with trace.span("compile.readback"):
        reduced = np.asarray(reduced)
    series = sop.epilogue(reduced)
    STATS["fused_runs"] += 1
    note = (f"compile: fused evolution[{sop.name}] (T={T}, "
            f"{'cache hit' if hit_before else 'traced'})")
    return (ts, series), (note,)


def _budget_miss(op: FusedOp, operand, T: int):
    """Dense-adjacency programs refuse shapes whose (T, N, N) stack
    would blow the budget — the staged path handles them instead."""
    if isinstance(op, TrianglesOp) and T * len(operand) ** 2 > DENSE_BUDGET:
        return MISS, (f"compile: staged compute[{op.name}] (dense stack "
                      f"T*N^2={T * len(operand) ** 2} exceeds budget)",)
    return None
