"""Batched aggregators: map (shard-local) / reduce (cross-shard) / present.

Replaces the reference's RowAggregator family + fastReduce
(reference: query/exec/aggregator/RowAggregator.scala:29,114-141,
exec/AggrOverRangeVectors.scala:151-277).  The map phase runs device
segment-reductions over [S, T] batches; partial state is a dict of [G, ...]
arrays mergeable across shards (the analog of the reference's transportable
aggregate rows); present converts final state to a PeriodicBatch.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from filodb_tpu.ops import aggregate as segops
from filodb_tpu.ops.windows import StepRange
from filodb_tpu.query.logical import AggregationOperator as Op
from filodb_tpu.query.model import PeriodicBatch, QueryError
from filodb_tpu.utils.observability import TRACER


@dataclasses.dataclass
class AggPartialBatch:
    """Mergeable aggregation state: per-group arrays keyed by name."""

    op: Op
    params: tuple
    group_keys: list[dict]
    steps: StepRange
    state: dict[str, np.ndarray]
    # series keys for ops whose reduce needs original series (topk/quantile)
    series_keys: Optional[list[dict]] = None
    # bucket tops when the state carries histogram sums ("hist_sum")
    bucket_tops: Optional[np.ndarray] = None

    @property
    def num_series(self) -> int:
        return len(self.group_keys)


def grouping_key(tags: dict, by: tuple, without: tuple, metric_col: str = "_metric_"):
    """The output key of by/without grouping (reference: AggregateMapReduce
    grouping): plain aggregation collapses to one group; ``without`` keeps
    the complement (minus the metric name); ``by`` keeps exactly those."""
    if by:
        return {k: tags.get(k, "") for k in by if k in tags}
    if without:
        drop = set(without) | {metric_col}
        return {k: v for k, v in tags.items() if k not in drop}
    return {}


def _group(keys: Sequence[dict], by, without, limit: int):
    gk = [tuple(sorted(grouping_key(t, by, without).items())) for t in keys]
    ids, uniq = segops.group_ids(gk)
    if len(uniq) > limit:
        raise QueryError("", f"group-by cardinality {len(uniq)} exceeds limit {limit}")
    return ids, [dict(u) for u in uniq]


def _padded_ids(ids: np.ndarray, total_series: int, num_groups: int) -> jnp.ndarray:
    """Pad ids to the padded series axis; padding rows land in a garbage
    group that is sliced off after the segment reduction."""
    out = np.full(total_series, num_groups, dtype=np.int32)
    out[:len(ids)] = ids
    return jnp.asarray(out)


class Aggregator:
    op: Op

    def map(self, batch: PeriodicBatch, by, without, params, limit) -> AggPartialBatch:
        raise NotImplementedError

    def reduce(self, partials: list[AggPartialBatch]) -> AggPartialBatch:
        raise NotImplementedError

    def present(self, partial: AggPartialBatch) -> PeriodicBatch:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# moment-based aggregators share alignment machinery
# ---------------------------------------------------------------------------

def _align(partials: list[AggPartialBatch], fill: float):
    """Union group keys; each partial's arrays scatter into union rows."""
    index: dict[tuple, int] = {}
    for p in partials:
        for k in p.group_keys:
            index.setdefault(tuple(sorted(k.items())), len(index))
    G = len(index)
    names = partials[0].state.keys()
    aligned = {n: [] for n in names}
    for p in partials:
        rows = np.array([index[tuple(sorted(k.items()))] for k in p.group_keys],
                        dtype=np.int64)
        for n in names:
            arr = np.asarray(p.state[n])
            f = -1 if np.issubdtype(arr.dtype, np.integer) else fill
            out = np.full((G,) + arr.shape[1:], f, dtype=arr.dtype)
            if len(rows):
                out[rows] = arr
            aligned[n].append(out)
    keys = [dict(k) for k in index.keys()]
    return keys, aligned


def _nansum_stack(arrs: list[np.ndarray]) -> np.ndarray:
    stack = np.stack(arrs)
    allnan = np.all(np.isnan(stack), axis=0)
    s = np.nansum(stack, axis=0)
    return np.where(allnan, np.nan, s)


class MomentAggregator(Aggregator):
    """sum/count/min/max/avg/stddev/stdvar/group via (sum, sumsq, count,
    min, max) moments — one implementation, different presenters."""

    def __init__(self, op: Op):
        self.op = op

    _NEEDS = {
        Op.SUM: ("sum", "count"), Op.COUNT: ("count",),
        Op.MIN: ("min",), Op.MAX: ("max",),
        Op.AVG: ("sum", "count"), Op.GROUP: ("count",),
        Op.STDDEV: ("sum", "sumsq", "count"),
        Op.STDVAR: ("sum", "sumsq", "count"),
    }

    def map(self, batch, by, without, params, limit):
        if batch.hist is not None:
            return self._map_hist(batch, by, without, params, limit)
        ids, keys = _group(batch.keys, by, without, limit)
        G = len(keys)
        vals = jnp.asarray(batch.values)
        pids = _padded_ids(ids, vals.shape[0], G)
        state = {}
        needs = self._NEEDS[self.op]
        if "sum" in needs or "count" in needs:
            fin = jnp.isfinite(vals)
            s = jax.ops.segment_sum(jnp.where(fin, vals, 0.0), pids, G + 1)[:G]
            n = jax.ops.segment_sum(fin.astype(vals.dtype), pids, G + 1)[:G]
            if "sum" in needs:
                state["sum"] = np.asarray(s)
            if "count" in needs:
                state["count"] = np.asarray(n)
        if "sumsq" in needs:
            fin = jnp.isfinite(vals)
            sq = jax.ops.segment_sum(jnp.where(fin, vals * vals, 0.0), pids,
                                     G + 1)[:G]
            state["sumsq"] = np.asarray(sq)
        if "min" in needs:
            state["min"] = np.asarray(
                segops.seg_min(vals, pids, G + 1)[:G])
        if "max" in needs:
            state["max"] = np.asarray(
                segops.seg_max(vals, pids, G + 1)[:G])
        return AggPartialBatch(self.op, params, keys, batch.steps, state)

    def _map_hist(self, batch, by, without, params, limit):
        """Bucket-wise histogram sum (reference: exec/aggregator/
        RowAggregator.scala HistSumRowAggregator).  Only sum is defined
        over first-class histogram series."""
        if self.op != Op.SUM:
            raise QueryError(
                "", f"{self.op.name.lower()}() over histogram series is not "
                    "supported (only sum; use hist_to_prom_vectors for "
                    "per-bucket series)")
        ids, keys = _group(batch.keys, by, without, limit)
        G = len(keys)
        h = jnp.asarray(np.asarray(batch.hist)[:len(batch.keys)])
        idsj = jnp.asarray(ids.astype(np.int32))
        fin = jnp.isfinite(h[..., -1])                   # [S, T]
        hs = jax.ops.segment_sum(jnp.where(fin[..., None], h, 0.0), idsj, G)
        n = jax.ops.segment_sum(fin.astype(h.dtype), idsj, G)
        state = {"hist_sum": np.asarray(hs), "count": np.asarray(n)}
        return AggPartialBatch(self.op, params, keys, batch.steps, state,
                               bucket_tops=np.asarray(batch.bucket_tops))

    @staticmethod
    def _align_hist_widths(partials):
        """Edge-pad cumulative bucket matrices to the widest scheme (the
        same convention as scan_batch / merge_batches): a narrower
        histogram's top bucket already holds the total count."""
        hists = [p for p in partials if "hist_sum" in p.state]
        if not hists:
            return None
        if len(hists) != len(partials):
            raise QueryError("", "cannot reduce histogram and scalar "
                                 "aggregates together (mixed schemas)")
        widest = max(hists, key=lambda p: p.state["hist_sum"].shape[-1])
        bmax = widest.state["hist_sum"].shape[-1]
        for i, p in enumerate(partials):
            h = np.asarray(p.state["hist_sum"])
            if h.shape[-1] < bmax:
                padded = np.pad(
                    h, [(0, 0)] * (h.ndim - 1) + [(0, bmax - h.shape[-1])],
                    mode="edge")
                # copy-on-write: the input partial stays self-consistent
                # (its own hist_sum width must keep matching bucket_tops)
                partials[i] = dataclasses.replace(
                    p, state={**p.state, "hist_sum": padded})
        return widest.bucket_tops

    def reduce(self, partials):
        first = partials[0]
        tops = self._align_hist_widths(partials)
        keys, aligned = _align(partials, np.nan)
        state = {}
        for n, arrs in aligned.items():
            if n in ("sum", "sumsq", "hist_sum"):
                state[n] = _nansum_stack(arrs)
            elif n == "count":
                zeroed = [np.nan_to_num(a, nan=0.0) for a in arrs]
                state[n] = np.sum(np.stack(zeroed), axis=0)
            elif n == "min":
                state[n] = np.nanmin(np.stack(arrs), axis=0)
            elif n == "max":
                state[n] = np.nanmax(np.stack(arrs), axis=0)
        return AggPartialBatch(self.op, first.params, keys, first.steps, state,
                               bucket_tops=tops)

    def present(self, p):
        s = p.state
        if "hist_sum" in s:
            n = np.asarray(s["count"])
            hist = np.where(n[..., None] > 0, s["hist_sum"], np.nan)
            return PeriodicBatch(p.group_keys, p.steps,
                                 np.full(n.shape, np.nan), hist=hist,
                                 bucket_tops=p.bucket_tops)
        if self.op == Op.SUM:
            vals = np.where(s["count"] > 0, s["sum"], np.nan)
        elif self.op == Op.COUNT:
            vals = np.where(s["count"] > 0, s["count"], np.nan)
        elif self.op == Op.GROUP:
            vals = np.where(s["count"] > 0, 1.0, np.nan)
        elif self.op == Op.MIN:
            vals = s["min"]
        elif self.op == Op.MAX:
            vals = s["max"]
        elif self.op == Op.AVG:
            n = s["count"]
            vals = np.where(n > 0, s["sum"] / np.maximum(n, 1.0), np.nan)
        else:  # stddev / stdvar
            n = s["count"]
            nsafe = np.maximum(n, 1.0)
            mean = s["sum"] / nsafe
            var = np.maximum(s["sumsq"] / nsafe - mean * mean, 0.0)
            if self.op == Op.STDDEV:
                var = np.sqrt(var)
            vals = np.where(n > 0, var, np.nan)
        return PeriodicBatch(p.group_keys, p.steps, vals)


class TopBottomKAggregator(Aggregator):
    """topk/bottomk: map keeps k candidate (value, series) slots per group per
    step; reduce concatenates candidate slots and re-selects; present emits
    the original contributing series with NaN at unselected steps
    (reference: TopBottomKRowAggregator)."""

    def __init__(self, op: Op):
        self.op = op

    def map(self, batch, by, without, params, limit):
        k = int(params[0])
        ids, keys = _group(batch.keys, by, without, limit)
        G = len(keys)
        vals = jnp.asarray(batch.values)
        pids = _padded_ids(ids, vals.shape[0], G)
        values, sidx = segops.seg_topk(vals, pids, G + 1, k,
                                       bottom=self.op == Op.BOTTOMK)
        return AggPartialBatch(self.op, params, keys, batch.steps,
                               {"values": np.asarray(values[:G]),
                                "sidx": np.asarray(sidx[:G])},
                               series_keys=list(batch.keys))

    def reduce(self, partials):
        k = int(partials[0].params[0])
        # remap per-partial series indices into a combined series key list
        all_keys: list[dict] = []
        offsets = []
        for p in partials:
            offsets.append(len(all_keys))
            all_keys.extend(p.series_keys or [])
        keys, aligned = _align(partials, np.nan)
        cands_v, cands_i = [], []
        for p, off, av, ai in zip(partials, offsets, aligned["values"],
                                  aligned["sidx"]):
            sidx = ai.astype(np.int64)
            remapped = np.where(sidx >= 0, sidx + off, -1)
            cands_v.append(av)
            cands_i.append(remapped)
        V = np.concatenate(cands_v, axis=1)   # [G, sum_k, T]
        I = np.concatenate(cands_i, axis=1)
        sign = -1.0 if self.op == Op.BOTTOMK else 1.0
        work = np.where(np.isfinite(V), V * sign, -np.inf)
        order = np.argsort(-work, axis=1, kind="stable")[:, :k]   # [G,k,T]
        top_v = np.take_along_axis(V, order, axis=1)
        top_i = np.take_along_axis(I, order, axis=1)
        top_w = np.take_along_axis(work, order, axis=1)
        top_v = np.where(np.isfinite(top_w), top_v, np.nan)
        top_i = np.where(np.isfinite(top_w), top_i, -1)
        return AggPartialBatch(self.op, partials[0].params, keys,
                               partials[0].steps,
                               {"values": top_v, "sidx": top_i.astype(np.int32)},
                               series_keys=all_keys)

    def present(self, p):
        V, I = p.state["values"], p.state["sidx"].astype(np.int64)
        skeys = p.series_keys or []
        G, k, T = V.shape
        out_keys: list[dict] = []
        rows: list[np.ndarray] = []
        import warnings
        for g in range(G):
            used = np.unique(I[g])
            for s in used:
                if s < 0:
                    continue
                row = np.full(T, np.nan)
                mask = I[g] == s                     # [k, T]
                sel = np.where(mask, V[g], np.nan)
                if mask.any():
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", RuntimeWarning)
                        row = np.nanmax(sel, axis=0)
                out_keys.append(skeys[int(s)])
                rows.append(row)
        vals = np.stack(rows) if rows else np.empty((0, T))
        return PeriodicBatch(out_keys, p.steps, vals)


def _dense_members_map(op, batch, by, without, params, limit,
                       grouped=None):
    """Per-group dense member matrix [G, M, T] (exact-path partial).
    ``grouped`` lets callers pass precomputed (ids, keys, vals, M)."""
    if grouped is None:
        ids, keys = _group(batch.keys, by, without, limit)
        vals = np.asarray(batch.values)[:len(batch.keys)]
        counts = np.bincount(ids, minlength=len(keys)) if len(ids) \
            else np.zeros(len(keys), int)
        M = int(counts.max()) if len(keys) else 0
    else:
        ids, keys, vals, M = grouped
    G = len(keys)
    T = vals.shape[1]
    dense = np.full((G, max(M, 1), T), np.nan)
    pos = np.zeros(G, dtype=np.int64)
    for s, g in enumerate(ids):
        dense[g, pos[g]] = vals[s]
        pos[g] += 1
    return AggPartialBatch(op, params, keys, batch.steps, {"members": dense})


class QuantileAggregator(Aggregator):
    """Quantile with bounded memory: small groups stay exact (dense member
    matrix + one sort, :func:`_nan_quantile`); past ``exact_members``
    members per group the partial switches to a mergeable t-digest
    sketch, O(G*T*C) no matter the cardinality (reference: QuantileRowAggregator's TDigest partials,
    exec/aggregator/RowAggregator.scala).  Reduce handles mixed partials
    by sketching the exact side."""

    op = Op.QUANTILE
    exact_members = 128       # per-group member budget before sketching
    compression = 128

    def map(self, batch, by, without, params, limit):
        from filodb_tpu.query import tdigest

        ids, keys = _group(batch.keys, by, without, limit)
        G = len(keys)
        vals = np.asarray(batch.values)[:len(batch.keys)]
        counts = np.bincount(ids, minlength=G) if len(ids) \
            else np.zeros(G, int)
        M = int(counts.max()) if G else 0
        if M <= self.exact_members:
            return _dense_members_map(self.op, batch, by, without, params,
                                      limit, grouped=(ids, keys, vals, M))
        d = tdigest.from_values(vals, np.asarray(ids), G, self.compression)
        return AggPartialBatch(self.op, params, keys, batch.steps,
                               {"td_means": d.means, "td_weights": d.weights})

    @staticmethod
    def _is_digest(p) -> bool:
        return "td_means" in p.state

    def _to_digest_state(self, p) -> dict:
        from filodb_tpu.query import tdigest

        if self._is_digest(p):
            return p.state
        d = tdigest.from_members(p.state["members"], self.compression)
        return {"td_means": d.means, "td_weights": d.weights}

    def reduce(self, partials):
        from filodb_tpu.query import tdigest

        if not any(self._is_digest(p) for p in partials):
            total = sum(p.state["members"].shape[1] for p in partials)
            if total <= self.exact_members:
                keys, aligned = _align(partials, np.nan)
                members = np.concatenate(aligned["members"], axis=1)
                return AggPartialBatch(self.op, partials[0].params, keys,
                                       partials[0].steps,
                                       {"members": members})
        # sketch path: convert any exact partials, then cell-wise merge
        norm = [AggPartialBatch(p.op, p.params, p.group_keys, p.steps,
                                self._to_digest_state(p))
                for p in partials]
        keys, aligned = _align(norm, np.nan)
        acc = tdigest.TDigest(aligned["td_means"][0],
                              np.nan_to_num(aligned["td_weights"][0]))
        for m, w in zip(aligned["td_means"][1:], aligned["td_weights"][1:]):
            acc = tdigest.merge(acc, tdigest.TDigest(m, np.nan_to_num(w)))
        return AggPartialBatch(self.op, partials[0].params, keys,
                               partials[0].steps,
                               {"td_means": acc.means,
                                "td_weights": acc.weights})

    def present(self, p):
        q = float(p.params[0])
        with TRACER.stage("quantile.present") as sp:
            if self._is_digest(p):
                from filodb_tpu.query import tdigest
                means, weights = p.state["td_means"], p.state["td_weights"]
                G, T = means.shape[:2]
                sp.tag(path="sketch", groups=G, steps=T, members=int(
                    np.nan_to_num(weights).sum(axis=-1).max(initial=0)))
                vals = tdigest.quantile(tdigest.TDigest(means, weights), q)
            else:
                members = p.state["members"]
                G, M, T = members.shape
                sp.tag(path="exact", groups=G, members=M, steps=T)
                vals = _nan_quantile(members, q)
        return PeriodicBatch(p.group_keys, p.steps, vals)


def _nan_quantile(members: np.ndarray, q: float) -> np.ndarray:
    """Quantile ``q`` of ``members`` [G, M, T] along the member axis, NaN
    skipped: [G, T], NaN where a cell has no member.  Bit for bit what
    ``np.nanquantile(members, q, axis=1)`` (method ``linear``) gives, in
    a fixed number of whole-array calls: ``nanquantile`` with an axis is
    ``np.apply_along_axis`` over a Python function, three NumPy calls a
    step that drop the interpreter lock, and under the served path's
    dozen threads every drop is ~1.3 ms until the lock comes back (69
    drops, 72-84 ms, for 1.4 ms of work: PERF.md section 6, PR 30).
    NumPy drops it in any call over more than 500 elements and in every
    copy, sort and ``arange``: here the copy, the sort and one ``arange``
    are the only such calls while no cell of a panel lacks a member."""
    if not 0.0 <= q <= 1.0:
        raise ValueError("Quantiles must be in the range [0, 1]")
    G, M, T = members.shape
    s = members.transpose(0, 2, 1).copy().reshape(G * T, M)  # a row a cell
    s.sort(axis=-1)                             # NaN sorts last
    last = np.full(G * T, M - 1)                # -1: the cell is empty
    short = np.isnan(s[:, -1])                  # only these are counted
    last[short] -= np.isnan(s[short]).sum(axis=-1)
    virtual = last * q
    lo = np.floor(virtual)
    # an empty cell reads its first NaN
    idx = np.maximum(np.stack((lo, np.minimum(lo + 1, last))), 0)
    a, b = s.reshape(-1)[idx.astype(np.intp) + np.arange(G * T) * M]
    # NumPy's own rule, both branches; at the largest member it counts
    # the weight from index -1, which decides the sign of a zero
    t = virtual - np.where(virtual >= last, -1.0, lo)
    with np.errstate(invalid="ignore"):         # inf - inf
        d = b - a
        vals = np.where(t >= 0.5, b - d * (1 - t), a + d * t)
    return vals.reshape(G, T)


def members_state(vals2d: np.ndarray, gids: np.ndarray,
                  num_groups: int) -> dict:
    """The exact quantile's partial from windowed series values:
    ``vals2d`` [S, T] stepped values, ``gids`` [S] group per series ->
    {"members": [G, M, T]}, NaN beyond a group's own members (the state
    :func:`_dense_members_map` builds a series at a time).  For the mesh
    paths, which hold every shard's lanes at once."""
    gids = np.asarray(gids, dtype=np.int64)
    G = max(int(num_groups), 1)
    counts = np.bincount(gids, minlength=G)
    order = np.argsort(gids, kind="stable")
    # a member's place within its group, in the groups' order
    place = np.arange(len(gids)) - np.repeat(np.cumsum(counts) - counts,
                                             counts)
    dense = np.full((G, max(int(counts.max(initial=0)), 1),
                     vals2d.shape[1]), np.nan)
    dense[gids[order], place] = vals2d[order]
    return {"members": dense}


# count_values guards: the (group, value, step) count cube is bounded by
# the response itself (one output series per distinct (group, value)), so
# exceeding these is a cardinality error, not an OOM (the reference's
# CountValuesRowAggregator map would blow its RowKeyMap the same way)
CV_MAX_DISTINCT = 65_536
CV_MAX_STATE_BYTES = 1 << 31


def count_values_state(vals2d: np.ndarray, gids: np.ndarray,
                       num_groups: int) -> dict:
    """Vectorized count_values partial from windowed series values.

    ``vals2d`` [S, T] stepped values (NaN = no sample), ``gids`` [S]
    group per series.  One np.unique + one bincount over the whole
    matrix — no per-series Python loop and no dense [G, M, T] member
    cube (VERDICT r4 weak #5 / next #8); the state is the
    (value, group, step) count tensor the reference's
    CountValuesRowAggregator carries as mergeable (value -> count) rows.
    Returns {"cv_vals": [U] sorted distinct values,
    "cv_counts": [G, U, T] float64}."""
    G = max(int(num_groups), 1)
    vals2d = np.asarray(vals2d)
    T = vals2d.shape[1] if vals2d.ndim == 2 else 0
    fin = np.isfinite(vals2d)
    if not fin.any():
        return {"cv_vals": np.empty(0, np.float64),
                "cv_counts": np.zeros((G, 0, T), np.float64)}
    uniq, inv = np.unique(vals2d[fin], return_inverse=True)
    U = len(uniq)
    if U > CV_MAX_DISTINCT or G * U * T * 8 > CV_MAX_STATE_BYTES:
        raise QueryError("", f"count_values cardinality too large "
                             f"({U} distinct values x {G} groups)")
    s_idx, t_idx = np.nonzero(fin)
    g_idx = np.asarray(gids, dtype=np.int64)[s_idx]
    flat = (g_idx * U + inv.ravel()) * T + t_idx
    counts = np.bincount(flat, minlength=G * U * T).astype(np.float64)
    return {"cv_vals": uniq.astype(np.float64),
            "cv_counts": counts.reshape(G, U, T)}


class CountValuesAggregator(Aggregator):
    """count_values("label", v): per-step count of each distinct value
    (reference: CountValuesRowAggregator).  Two partial forms: the exact
    member pass-through ([G, M, T] "members", the single-batch map) and
    the counted form ({"cv_vals", "cv_counts"}, produced by the resident
    mesh path / :func:`count_values_state`); reduce normalizes to the
    counted form whenever any input carries it."""

    op = Op.COUNT_VALUES

    def map(self, batch, by, without, params, limit):
        # exact values pass through as the COUNTED form: one np.unique +
        # bincount over the [S, T] matrix, no per-series loop and no
        # dense [G, M, T] member cube at high cardinality
        ids, keys = _group(batch.keys, by, without, limit)
        vals = np.asarray(batch.values)[:len(batch.keys)]
        state = count_values_state(vals, ids, len(keys))
        return AggPartialBatch(self.op, params, keys, batch.steps, state)

    @staticmethod
    def _is_cv(p) -> bool:
        return "cv_vals" in p.state

    @staticmethod
    def _to_cv_state(p) -> dict:
        if "cv_vals" in p.state:
            return p.state
        members = np.asarray(p.state["members"])        # [G, M, T]
        G, _M, T = members.shape
        return count_values_state(members.reshape(-1, T),
                                  np.repeat(np.arange(G), _M), G)

    def reduce(self, partials):
        if not any(self._is_cv(p) for p in partials):
            keys, aligned = _align(partials, np.nan)
            members = np.concatenate(aligned["members"], axis=1)
            return AggPartialBatch(self.op, partials[0].params, keys,
                                   partials[0].steps, {"members": members})
        index: dict[tuple, int] = {}
        for p in partials:
            for k in p.group_keys:
                index.setdefault(tuple(sorted(k.items())), len(index))
        G = len(index)
        states = [self._to_cv_state(p) for p in partials]
        all_vals = np.unique(np.concatenate(
            [s["cv_vals"] for s in states]))
        U = len(all_vals)
        T = states[0]["cv_counts"].shape[-1]
        if U > CV_MAX_DISTINCT or G * U * T * 8 > CV_MAX_STATE_BYTES:
            raise QueryError("", f"count_values cardinality too large "
                                 f"({U} distinct values x {G} groups)")
        out = np.zeros((G, U, T), np.float64)
        for p, s in zip(partials, states):
            rows = [index[tuple(sorted(k.items()))] for k in p.group_keys]
            cols = np.searchsorted(all_vals, s["cv_vals"])
            if len(rows) and len(cols):
                out[np.ix_(rows, cols, np.arange(T))] += s["cv_counts"]
        return AggPartialBatch(self.op, partials[0].params,
                               [dict(k) for k in index], partials[0].steps,
                               {"cv_vals": all_vals, "cv_counts": out})

    def present(self, p):
        label = str(p.params[0])
        if self._is_cv(p):
            uniq = p.state["cv_vals"]
            counts = p.state["cv_counts"]       # [G, U, T]
            T = counts.shape[-1]
            out_keys, rows = [], []
            present_mask = counts.sum(axis=2) > 0          # [G, U]
            for g, u in zip(*np.nonzero(present_mask)):
                key = dict(p.group_keys[g])
                key[label] = _fmt_value(float(uniq[u]))
                out_keys.append(key)
                cnt = counts[g, u]
                rows.append(np.where(cnt > 0, cnt, np.nan))
            valsarr = np.stack(rows) if rows else np.empty((0, T))
            return PeriodicBatch(out_keys, p.steps, valsarr)
        members = p.state["members"]            # [G, M, T]
        G, M, T = members.shape
        out_keys, rows = [], []
        for g in range(G):
            vals = members[g]
            uniq = np.unique(vals[np.isfinite(vals)])
            for u in uniq:
                cnt = np.sum(vals == u, axis=0).astype(float)  # [T]
                key = dict(p.group_keys[g])
                key[label] = _fmt_value(float(u))
                out_keys.append(key)
                rows.append(np.where(cnt > 0, cnt, np.nan))
        valsarr = np.stack(rows) if rows else np.empty((0, T))
        return PeriodicBatch(out_keys, p.steps, valsarr)


def _fmt_value(v: float) -> str:
    return str(int(v)) if v == int(v) else repr(v)


_AGGREGATORS = {
    **{op: (lambda op=op: MomentAggregator(op)) for op in
       (Op.SUM, Op.COUNT, Op.MIN, Op.MAX, Op.AVG, Op.STDDEV, Op.STDVAR,
        Op.GROUP)},
    Op.TOPK: lambda: TopBottomKAggregator(Op.TOPK),
    Op.BOTTOMK: lambda: TopBottomKAggregator(Op.BOTTOMK),
    Op.QUANTILE: lambda: QuantileAggregator(),
    Op.COUNT_VALUES: lambda: CountValuesAggregator(),
}


def aggregator_for(op: Op) -> Aggregator:
    try:
        return _AGGREGATORS[op]()
    except KeyError:
        raise ValueError(f"unsupported aggregation operator {op}")
