"""MeshAggregateExec: the planner's ICI-collective serving path.

Fuses every LOCAL shard's leaf pipeline of an aggregate query —
scan -> window -> per-shard aggregate -> cross-shard reduce — into ONE
SPMD mesh program (parallel/mesh.py), replacing N per-shard ExecPlan
children + host-side reduce with device collectives riding ICI
(reference: the scatter-gather tree of SingleClusterPlanner.scala:223-258
+ ReduceAggregateExec, collapsed into lax.psum/pmin/pmax).

The node emits the same mergeable AggPartialBatch the per-shard path
produces, so it composes under ReduceAggregateExec next to REMOTE
shards' HTTP-dispatched partials — one cluster query can mix both data
planes, exactly like the reference mixes local and remote children.

Compressed residents (ISSUE 3): the GRID_MESH_ALL_OPS family serves
from XOR-class packed blocks without a decode-then-requery round trip —
``shard.mesh_grid_plan`` stages the decoded value plane ON DEVICE once
(memoized; repeat queries perform zero host decode and zero re-upload),
and uniform-phase plans never stage a ts plane at all (the SPMD program
ships a 1-row dummy; see parallel/meshgrid.py and doc/kernel.md §2).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import numpy as np

from filodb_tpu.core.filters import ColumnFilter
from filodb_tpu.ops.windows import StepRange
from filodb_tpu.query import rangefns
from filodb_tpu.query.aggregators import AggPartialBatch, grouping_key
from filodb_tpu.query.exec import ExecContext, ExecPlan, leaf_scan
from filodb_tpu.query.logical import (AggregationOperator, RangeFunctionId)
from filodb_tpu.query.model import QueryContext
from filodb_tpu.utils.observability import TRACER

# the steps of one fabric request (doc/observability.md "Stage spans"):
# a request the fabric served carries all six, 0.0 for a step its rung
# skipped; ``mesh.plan_build``, ``mesh.stage`` and ``mesh.assemble`` only
# where a memo missed
MESH_STAGES = ("mesh.collect", "mesh.prepare", "mesh.dispatch",
               "mesh.device_wait", "mesh.readback", "mesh.present")

# aggregates with a distributive psum/pmin/pmax form (mesh.partial_state_names)
MESH_OPS = (AggregationOperator.SUM, AggregationOperator.COUNT,
            AggregationOperator.AVG, AggregationOperator.MIN,
            AggregationOperator.MAX, AggregationOperator.STDDEV,
            AggregationOperator.STDVAR, AggregationOperator.GROUP)
# aggregates with a non-psum mesh partial: k-heap merge (topk/bottomk),
# member pass-through (count_values; quantile up to ``exact_members`` a
# group, a t-digest merge past it) — the full RowAggregator family
# (reference RowAggregator.scala:114-141)
_K_OPS = (AggregationOperator.TOPK, AggregationOperator.BOTTOMK)
_MEMBER_OPS = (AggregationOperator.QUANTILE,
               AggregationOperator.COUNT_VALUES)


def mesh_supported(operator: AggregationOperator,
                   function: Optional[RangeFunctionId],
                   params: tuple) -> bool:
    if operator in _K_OPS:
        ok = (len(params) == 1
              and float(params[0]) == int(float(params[0]))
              and int(float(params[0])) >= 1)
    elif operator in _MEMBER_OPS:
        ok = len(params) == 1
    else:
        ok = operator in MESH_OPS and not params
    return ok and rangefns.supported(function, hist=False)


# Fabric breaker (tentpole): tripped the first time a FUSED program
# fails to build or dispatch on this backend — every later query runs
# the always-correct scatter-gather fallback instead of re-discovering
# the failure at serve time.  The fused path is an optimization, never
# a correctness dependency (same contract as devicestore._PACKED_BROKEN).
FABRIC_BREAKER = {"open": False, "trips": 0}


def trip_fabric_breaker(exc: Exception) -> None:
    from filodb_tpu.parallel import meshgrid
    from filodb_tpu.utils.devicewatch import FLIGHT
    FABRIC_BREAKER["open"] = True
    FABRIC_BREAKER["trips"] += 1
    meshgrid._mm()["breaker"].set(1.0)
    FLIGHT.record("mesh.breaker_trip", error=str(exc)[:200])


def reset_fabric_breaker() -> None:
    """Admin/test reset (e.g. after a backend or driver change)."""
    from filodb_tpu.parallel import meshgrid
    FABRIC_BREAKER["open"] = False
    meshgrid._mm()["breaker"].set(0.0)


@functools.lru_cache(maxsize=16)
def mesh_placement(generation: int, num_devices: int):
    """shard -> mesh-device slot, keyed on
    ``ShardMapper.topology_generation``: a live split commits by bumping
    the generation, so the first post-cutover query atomically computes
    placement under the NEW shard space (children land on their own
    slots) while in-flight queries planned pre-cutover keep the old
    placement — they detect the bump via ``_topology_stale`` and serve
    per-shard instead of pinning residents to slots about to move."""
    def place(shard_num: int) -> int:
        return shard_num % num_devices
    return place


class MeshAggregateExec(ExecPlan):
    """All local shards of one windowed aggregate as one mesh program."""

    def __init__(self, dataset: str, shards: Sequence[int],
                 filters: Sequence[ColumnFilter], scan_start_ms: int,
                 scan_end_ms: int, start_ms: int, step_ms: int, end_ms: int,
                 operator: AggregationOperator,
                 window_ms: Optional[int] = None,
                 function: Optional[RangeFunctionId] = None,
                 function_args: tuple = (), offset_ms: int = 0,
                 by: tuple = (), without: tuple = (),
                 params: tuple = (), stale_ms: int = 300_000,
                 query_context: Optional[QueryContext] = None,
                 engine=None, mapper=None,
                 planned_generation: Optional[int] = None):
        super().__init__(query_context)
        self.dataset = dataset
        self.shards = list(shards)
        self.filters = list(filters)
        self.scan_start_ms = scan_start_ms
        self.scan_end_ms = scan_end_ms
        self.start_ms = start_ms
        self.step_ms = step_ms
        self.end_ms = end_ms
        self.operator = operator
        self.window_ms = window_ms
        self.function = function
        self.function_args = tuple(function_args)
        self.offset_ms = offset_ms
        self.by = tuple(by)
        self.without = tuple(without)
        self.params = tuple(params)
        self.stale_ms = stale_ms
        self._engine = engine
        # topology threading (satellite: generation-keyed placement) —
        # the planner stamps its snapshot's generation so execute-time
        # can detect a split cutover racing this query
        self.mapper = mapper
        self.planned_generation = planned_generation

    def _topology_stale(self) -> Optional[str]:
        """Reason the mesh path must stand down for this query, or None.
        A query planned pre-cutover ("generation") or overlapping a
        live reshard exclusion window ("exclusion") serves per-shard
        under its PLANNED topology view — the mesh placement/assembly
        would mix topologies mid-flight."""
        if self.mapper is None:
            return None
        if self.planned_generation is not None \
                and self.mapper.topology_generation != self.planned_generation:
            return "generation"
        live = self.mapper.topology
        if any(live.parent_exclusion(s) is not None for s in self.shards):
            return "exclusion"
        return None

    def _per_shard_fallback(self, ctx: ExecContext) -> list:
        """The always-correct scatter-gather form of this node: every
        shard runs the plain per-shard host pipeline, partials merge
        downstream.

        No reshard exclusions are stamped here, deliberately: the
        planner only emits a mesh node when its topology SNAPSHOT had
        no exclusions, so ``self.shards`` is a pre-cutover fan-out (the
        split parents).  A query planned pre-cutover must keep that
        snapshot's (no-exclusion) leaf stamps even when a cutover lands
        mid-flight — the parents hold a full superset until retirement
        purges them, so unfiltered parent scans stay exactly correct,
        while stamping the LIVE exclusions onto the OLD fan-out would
        drop every migrated series (their children are not among
        ``self.shards``).  Mixing topology views is the one thing the
        per-query snapshot contract forbids (planner._topology)."""
        out: list = []
        for shard_num in self.shards:
            out.extend(self._host_shard_partial(ctx, shard_num,
                                                reshard_to=None))
        return out

    def _args_str(self):
        return (f"dataset={self.dataset}, shards={self.shards}, "
                f"op={self.operator.name}, fn="
                f"{self.function.name if self.function else None}")

    def do_execute(self, ctx: ExecContext) -> list:
        # the mesh node is its plan's one data leaf, all local shards at
        # once: it owns the "scan" stage as a per-shard leaf does, and
        # the fabric's own stage spans (``mesh.*``) that end inside it
        # land in this query's timings under their names
        with leaf_scan(ctx):
            ctx.ensure_timings(MESH_STAGES)
            return self._serve(ctx)

    def _serve(self, ctx: ExecContext) -> list:
        from filodb_tpu.parallel import mesh as meshmod
        from filodb_tpu.parallel import meshgrid

        engine = self._engine or meshmod.default_engine()
        steps = StepRange(self.start_ms - self.offset_ms,
                          self.end_ms - self.offset_ms, self.step_ms)
        from filodb_tpu.query.transformers import effective_window_ms
        window = effective_window_ms(self.window_ms, self.stale_ms)
        report = StepRange(self.start_ms, self.end_ms, self.step_ms)
        union: dict[tuple, int] = {}
        out: list = []
        devices = list(engine.mesh.devices.flat)

        stale = self._topology_stale()
        if stale is not None:
            # planned against a topology that moved (split cutover /
            # active reshard exclusion): the mesh placement would mix
            # topologies mid-flight — serve per-shard under the planned
            # snapshot instead (always-correct scatter-gather)
            meshgrid._fallback(stale)
            from filodb_tpu.utils.devicewatch import FLIGHT
            FLIGHT.record("mesh.fallback", dataset=self.dataset,
                          reason=stale, shards=len(self.shards))
            return self._per_shard_fallback(ctx)

        grid_eligible = self.operator in meshgrid.GRID_MESH_ALL_OPS
        place = mesh_placement(self.planned_generation or 0, len(devices))
        limit = ctx.query_context.group_by_cardinality_limit
        entries = []                       # (shard, shard_num, lookup)
        plans, planned = [], []
        with TRACER.stage("mesh.collect", shards=len(self.shards)) as sp:
            for shard_num in self.shards:
                shard = ctx.memstore.get_shard(self.dataset, shard_num)
                if grid_eligible:
                    # mesh placement BEFORE any grid staging: blocks
                    # build on the device the SPMD program reads them
                    # from.  Only grid-capable queries pin — a host-path
                    # query must not invalidate resident state it will
                    # never use.
                    shard.pin_grid_device(devices[place(shard_num)])
                lookup = shard.lookup_partitions(self.filters,
                                                 self.scan_start_ms,
                                                 self.scan_end_ms)
                if len(lookup.part_ids) == 0:
                    continue
                entries.append((shard, shard_num, lookup))
            sp.tag(lanes_requested=sum(len(e[2].part_ids)
                                       for e in entries))

            # -- phase 1: the HBM-resident grid x mesh path (VERDICT r3
            # #1): every shard that can stage its scan in place — scalar
            # AND first-class histogram columns — contributes a
            # MeshShardPlan; ONE shard_map program serves them all with
            # zero per-query host->device upload.  Shards that can't
            # (irregular layouts, cold data, mixed bucket schemes) fall
            # back per-shard to the host-batch mesh below.
            for ent in entries if grid_eligible else ():
                shard, _num, lookup = ent
                gids = self._grid_group_ids(shard, lookup.part_ids, union)
                if len(union) > limit:
                    # enforce BEFORE compiling/dispatching a G-sized
                    # program (the limit protects the expensive path)
                    self._cardinality_error(ctx, len(union))
                plan = None
                if gids is not None:
                    plan = shard.mesh_grid_plan(
                        lookup.part_ids, self.function, steps.start,
                        steps.num_steps, steps.step, window, gids,
                        fargs=self.function_args)
                if plan is not None:
                    plans.append(plan)
                    planned.append(ent)
        host_entries = entries
        if grid_eligible:
            if plans:
                num_grid_groups = len(union)
                state = meshgrid.serve_grid_mesh(engine, plans,
                                                 num_grid_groups,
                                                 self.operator,
                                                 params=self.params)
                # flight recorder: whether the resident SPMD path served
                # (or demoted to host-batch) is the first question of
                # any mesh-latency postmortem
                from filodb_tpu.utils.devicewatch import FLIGHT
                FLIGHT.record("mesh.serve", dataset=self.dataset,
                              shards=len(plans), groups=num_grid_groups,
                              resident=state is not None)
                if state is not None:
                    keys = [dict(k) for k in
                            list(union)[:num_grid_groups]]
                    tops = state.pop("bucket_tops", None)
                    series_keys = None
                    if "_slots" in state:
                        series_keys = self._resolve_k_lanes(
                            state, plans, planned)
                    out.append(AggPartialBatch(self.operator,
                                               self.params, keys,
                                               report, state,
                                               series_keys=series_keys,
                                               bucket_tops=tops))
                    served = set(id(e) for e in planned)
                    host_entries = [e for e in entries
                                    if id(e) not in served]

        # -- phase 2: host-batch mesh path for the remaining shards
        Agg = AggregationOperator
        hist_in_mesh = (self.operator is Agg.SUM and not self.params
                        and not self.function_args
                        and rangefns.supported(self.function, hist=True))
        shard_batches = []
        group_ids = []
        tags_lists = []
        hist_batches = []
        hist_gids = []
        host_partials: list = []
        for shard, shard_num, lookup in host_entries:
            tags_list, batch = shard.scan_batch(
                lookup.part_ids, self.scan_start_ms, self.scan_end_ms)
            if batch is None:
                continue                    # genuinely empty range
            if batch.hist is not None and not hist_in_mesh:
                # histogram data under a shape the hist mesh program
                # can't take must NOT be dropped — run the per-shard
                # host path and merge its partial below
                host_partials.extend(self._host_shard_partial(ctx,
                                                              shard_num))
                continue
            gids = np.empty(len(tags_list), dtype=np.int32)
            for i, tags in enumerate(tags_list):
                key = tuple(sorted(grouping_key(tags, self.by,
                                                self.without).items()))
                gids[i] = union.setdefault(key, len(union))
            if batch.hist is not None:
                hist_batches.append(batch)
                hist_gids.append(gids)
            else:
                shard_batches.append(batch)
                group_ids.append(gids)
                tags_lists.append(tags_list)
        if not out and not shard_batches and not hist_batches \
                and not host_partials:
            return []
        if len(union) > limit:
            self._cardinality_error(ctx, len(union))
        out.extend(host_partials)
        keys = [dict(k) for k in union]
        G = max(len(union), 1)
        if hist_batches:
            state, tops = engine.window_hist_partials(
                hist_batches, hist_gids, G, steps, window,
                range_fn=self.function)
            out.append(AggPartialBatch(self.operator, self.params, keys,
                                       report, state, bucket_tops=tops))
        if shard_batches:
            if self.operator in _K_OPS:
                out.append(self._topk_partial(
                    engine, shard_batches, group_ids, tags_lists, keys,
                    steps, report, window))
            elif self.operator is Agg.QUANTILE:
                out.append(AggPartialBatch(
                    self.operator, self.params, keys, report,
                    self._quantile_state(engine, shard_batches, group_ids,
                                         tags_lists, G, steps, window)))
            elif self.operator is Agg.COUNT_VALUES:
                out.append(self._count_values_partial(
                    engine, shard_batches, group_ids, tags_lists, keys,
                    steps, report, window))
            else:
                state = engine.window_aggregate_partials(
                    shard_batches, group_ids, G, steps, window,
                    range_fn=self.function, agg_op=self.operator,
                    extra_args=self.function_args)
                out.append(AggPartialBatch(self.operator, self.params,
                                           keys, report, state))
        return out

    def _topk_partial(self, engine, shard_batches, group_ids, tags_lists,
                      keys, steps, report, window) -> AggPartialBatch:
        """topk/bottomk via the mesh k-heap program; sidx comes back as
        global (shard, series) row indices which map onto the flattened
        series-key list the reducer/presenter resolve against."""
        from filodb_tpu.query.logical import AggregationOperator as Agg
        k = int(float(self.params[0]))
        v, si, (Kp, S) = engine.window_topk_partials(
            shard_batches, group_ids, max(len(keys), 1), steps, window,
            k, bottom=self.operator is Agg.BOTTOMK,
            range_fn=self.function, extra_args=self.function_args)
        series_keys: list[dict] = []
        for kk in range(Kp):
            tl = tags_lists[kk] if kk < len(tags_lists) else []
            series_keys.extend(tl)
            series_keys.extend({} for _ in range(S - len(tl)))
        return AggPartialBatch(self.operator, self.params, keys, report,
                               {"values": v, "sidx": si},
                               series_keys=series_keys)

    @staticmethod
    def _member_ids(group_ids, tags_lists) -> np.ndarray:
        """[series] group ids of the host-fed shards' real series."""
        return np.concatenate(
            [gid[:len(tl)] for tl, gid in zip(tags_lists, group_ids)]) \
            if tags_lists else np.empty(0, np.int64)

    def _member_values(self, engine, shard_batches, tags_lists, steps,
                       window) -> np.ndarray:
        """scan+window on the host-fed mesh, every real series' stepped
        values read back [series, T], in ``_member_ids``' order."""
        stepped, (Kp, S) = engine.window_values(
            shard_batches, steps, window, range_fn=self.function,
            extra_args=self.function_args)
        rows = np.concatenate(
            [np.arange(len(tl), dtype=np.int64) + kk * S
             for kk, tl in enumerate(tags_lists)]) \
            if tags_lists else np.empty(0, np.int64)
        return stepped[rows]

    def _count_values_partial(self, engine, shard_batches, group_ids,
                              tags_lists, keys, steps, report,
                              window) -> AggPartialBatch:
        """count_values: scan+window on the mesh, vectorized
        (value, group, step) counting on host — exact values pass
        through like the reference's CountValuesRowAggregator, without
        a per-series loop or a dense member cube."""
        from filodb_tpu.query.aggregators import count_values_state
        state = count_values_state(
            self._member_values(engine, shard_batches, tags_lists, steps,
                                window),
            self._member_ids(group_ids, tags_lists), max(len(keys), 1))
        return AggPartialBatch(self.operator, self.params, keys, report,
                               state)

    def _quantile_state(self, engine, shard_batches, group_ids, tags_lists,
                        G: int, steps, window) -> dict:
        """quantile on the host-fed mesh: the members themselves while
        the largest group has at most ``exact_members`` of them over the
        shards here (the per-shard rung's rule, the resident fabric's
        too: meshgrid._exact_width), the merged t-digests past it."""
        from filodb_tpu.query.aggregators import (QuantileAggregator,
                                                  members_state)
        ids = self._member_ids(group_ids, tags_lists)
        if np.bincount(ids).max(initial=0) \
                <= QuantileAggregator.exact_members:
            return members_state(
                self._member_values(engine, shard_batches, tags_lists,
                                    steps, window), ids, G)
        m, w = engine.window_quantile_partials(
            shard_batches, group_ids, G, steps, window,
            range_fn=self.function, extra_args=self.function_args,
            compression=QuantileAggregator.compression)
        return {"td_means": m, "td_weights": w}

    def _resolve_k_lanes(self, state: dict, plans, planned) -> list[dict]:
        """Map the resident k-slot program's GLOBAL lane indices back to
        series tags: sidx value g decodes to (mesh slot g // lmax, lane
        g % lmax); the slot's MeshShardPlan knows which partition a lane
        was asked for (pid_of_lane), and the slot's shard resolves tags.  The state
        is rewritten in place to compact indices into the returned
        series-key list (the AggPartialBatch contract the host k-path
        uses).  Unresolvable lanes (partition concurrently evicted) are
        DROPPED (sidx -1) — the same thing the host path's present does
        with its padding slots."""
        slots = state.pop("_slots")
        lmax = state.pop("_lmax")
        sidx = state["sidx"]
        uniq = np.unique(sidx[sidx >= 0])
        series_keys: list[dict] = []
        remap = {}
        for g in uniq.tolist():
            slot, lane = divmod(int(g), lmax)
            tags = None
            pi = slots[slot] if slot < len(slots) else -1
            if pi >= 0:
                plan = plans[pi]
                shard = planned[pi][0]
                pid = plan.pid_of_lane(lane)
                if pid >= 0:
                    part = shard.grid_partition(pid)
                    if part is not None:
                        tags = part.tags
            if tags is None:
                remap[g] = -1
                continue
            remap[g] = len(series_keys)
            series_keys.append(tags)
        if len(remap):
            lut = np.full(int(uniq.max()) + 2, -1, np.int64)
            for g, i in remap.items():
                lut[g] = i
            state["sidx"] = np.where(sidx >= 0, lut[np.maximum(sidx, 0)],
                                     -1).astype(np.int32)
        else:
            state["sidx"] = sidx.astype(np.int32)
        # a dropped lane must not occupy a k-slot in a downstream reduce
        state["values"] = np.where(state["sidx"] >= 0, state["values"],
                                   np.nan)
        return series_keys

    def _cardinality_error(self, ctx, n: int):
        from filodb_tpu.query.model import QueryError
        limit = ctx.query_context.group_by_cardinality_limit
        raise QueryError(self.query_context.query_id,
                         f"group-by cardinality {n} exceeds "
                         f"limit {limit}")

    def _grid_group_ids(self, shard, part_ids, union: dict):
        """Group ids for the resident grid path, in ``part_ids`` order
        (the order devicestore assigns lanes); with no grouping the ONE
        id every series shares, as an int, and no array as long as the
        lookup.  Grows ``union`` in place; returns None when a partition
        vanished mid-query (the host path re-resolves via scan_batch)."""
        if not self.by and not self.without:
            return union.setdefault((), len(union))
        gids = np.empty(len(part_ids), dtype=np.int32)
        for i, pid in enumerate(part_ids):
            part = shard.grid_partition(int(pid))
            if part is None:
                return None
            key = tuple(sorted(grouping_key(part.tags, self.by,
                                            self.without).items()))
            gids[i] = union.setdefault(key, len(union))
        return gids

    def _host_shard_partial(self, ctx: ExecContext, shard_num: int,
                            reshard_to: Optional[tuple] = None) -> list:
        """Per-shard host pipeline for data the mesh program can't take
        (histogram value columns) and for topology/breaker fallbacks:
        leaf scan + PeriodicSamplesMapper + AggregateMapReduce, exactly
        the non-mesh plan shape.  ``reshard_to`` stamps the live
        topology's split-parent exclusion on the leaf (query/exec.py)."""
        from filodb_tpu.query.exec import MultiSchemaPartitionsExec
        from filodb_tpu.query.transformers import (AggregateMapReduce,
                                                   PeriodicSamplesMapper)
        leaf = MultiSchemaPartitionsExec(
            self.dataset, shard_num, self.filters, self.scan_start_ms,
            self.scan_end_ms, query_context=self.query_context,
            reshard_to=reshard_to)
        leaf.add_transformer(PeriodicSamplesMapper(
            self.start_ms, self.step_ms, self.end_ms,
            window_ms=self.window_ms, function=self.function,
            function_args=self.function_args, offset_ms=self.offset_ms))
        leaf.add_transformer(AggregateMapReduce(
            self.operator, self.params, self.by, self.without))
        return list(leaf.execute(ctx).batches)

    def _collect_plans(self, ctx: ExecContext):
        """Stage EVERY shard's resident MeshShardPlan — the
        all-or-nothing contract of the fused single-dispatch programs
        (one non-resident shard breaks the one-program story; the
        partial tier handles mixed residency instead).  Returns
        (engine, plans, union, report) or None when any shard with data
        cannot stage."""
        from filodb_tpu.parallel import mesh as meshmod
        from filodb_tpu.parallel import meshgrid
        from filodb_tpu.query.transformers import effective_window_ms

        engine = self._engine or meshmod.default_engine()
        steps = StepRange(self.start_ms - self.offset_ms,
                          self.end_ms - self.offset_ms, self.step_ms)
        window = effective_window_ms(self.window_ms, self.stale_ms)
        report = StepRange(self.start_ms, self.end_ms, self.step_ms)
        devices = list(engine.mesh.devices.flat)
        place = mesh_placement(self.planned_generation or 0, len(devices))
        limit = ctx.query_context.group_by_cardinality_limit
        union: dict[tuple, int] = {}
        plans = []
        with TRACER.stage("mesh.collect", shards=len(self.shards)) as sp:
            lanes = 0
            for shard_num in self.shards:
                shard = ctx.memstore.get_shard(self.dataset, shard_num)
                shard.pin_grid_device(devices[place(shard_num)])
                lookup = shard.lookup_partitions(self.filters,
                                                 self.scan_start_ms,
                                                 self.scan_end_ms)
                if len(lookup.part_ids) == 0:
                    continue
                lanes += len(lookup.part_ids)
                gids = self._grid_group_ids(shard, lookup.part_ids, union)
                if len(union) > limit:
                    self._cardinality_error(ctx, len(union))
                plan = None
                if gids is not None:
                    plan = shard.mesh_grid_plan(
                        lookup.part_ids, self.function, steps.start,
                        steps.num_steps, steps.step, window, gids,
                        fargs=self.function_args)
                if plan is None:
                    meshgrid._fallback("shape")
                    return None
                plans.append(plan)
            sp.tag(lanes_requested=lanes)
        return engine, plans, union, report


class MeshReduceExec(MeshAggregateExec):
    """The tentpole node: when EVERY child shard of an aggregation is
    mesh-resident on this host, the planner emits this node as the plan
    ROOT — leaf-scan -> window -> aggregate -> cross-shard reduce ->
    present compile into ONE device program (meshgrid.fused /
    meshgrid.fused_histq) and the only readback is the final [G, T]
    answer; N per-shard dispatches and the host reduce disappear.

    Serving ladder, every rung answer-equal: fused single dispatch ->
    partial mesh program + host reduce/present (non-fusable op or mixed
    residency) -> per-shard scatter-gather (breaker trip, topology
    moved mid-flight).  Unlike MeshAggregateExec this node returns
    PRESENTED batches — it IS the reduce+present, so the planner emits
    it with no ReduceAggregateExec / AggregatePresenter above it."""

    def __init__(self, *args, hist_phi: Optional[float] = None, **kwargs):
        super().__init__(*args, **kwargs)
        # histogram_quantile fusion: the planner folds the mapper's
        # static phi into the node so the quantile interpolation runs
        # inside the same device program as the bucket psum
        self.hist_phi = hist_phi

    def _args_str(self):
        phi = f", phi={self.hist_phi}" if self.hist_phi is not None else ""
        return super()._args_str() + phi

    def _serve(self, ctx: ExecContext) -> list:
        rung, batches = self._rung(ctx)
        # what is left for the host once a rung has answered: nothing on
        # the fused one (the program presented), reduce + present of the
        # partials on the other two
        with TRACER.stage("mesh.present", rung=rung):
            return batches if rung == "fused" \
                else self._present_host(batches)

    def _rung(self, ctx: ExecContext) -> tuple:
        """(the rung of the ladder that served, its batches: presented
        on ``fused``, partials on ``partial`` and ``per_shard``)."""
        from filodb_tpu.parallel import meshgrid
        from filodb_tpu.utils.devicewatch import FLIGHT

        reason = self._topology_stale() \
            or ("breaker" if FABRIC_BREAKER["open"] else None)
        if reason is not None:
            meshgrid._fallback(reason)
            FLIGHT.record("mesh.fallback", dataset=self.dataset,
                          reason=reason, shards=len(self.shards))
            return "per_shard", self._per_shard_fallback(ctx)
        if self.operator in meshgrid._PRESENT_AGGS and not self.params:
            try:
                fused = self._fused(ctx)
            except Exception as e:
                # the fused program is an optimization, never a
                # correctness dependency: trip the breaker and serve
                # this (and every later) query scatter-gather
                trip_fabric_breaker(e)
                return "per_shard", self._per_shard_fallback(ctx)
            if fused is not None:
                return "fused", fused
        # partial-tier rung: the mesh partial program(s) + host
        # reduce/present — exactly what ReduceAggregateExec +
        # AggregatePresenter compose over a MeshAggregateExec child
        return "partial", super()._serve(ctx)

    def _fused(self, ctx: ExecContext) -> Optional[list]:
        """The single-dispatch rung; None demotes to the partial tier."""
        from filodb_tpu.parallel import meshgrid
        from filodb_tpu.query.model import PeriodicBatch
        from filodb_tpu.utils.devicewatch import FLIGHT

        got = self._collect_plans(ctx)
        if got is None:
            return None
        engine, plans, union, report = got
        if not plans:
            return []                  # nothing matched on any shard
        vals = meshgrid.serve_grid_mesh_presented(
            engine, plans, len(union), self.operator,
            params=self.params, hist_phi=self.hist_phi)
        FLIGHT.record("mesh.fused", dataset=self.dataset,
                      shards=len(plans), groups=len(union),
                      served=vals is not None)
        if vals is None:
            return None
        keys = [dict(k) for k in union]
        return [PeriodicBatch(keys, report, vals)]

    def _present_host(self, batches: list) -> list:
        """Host reduce+present for the lower rungs — the same
        aggregator_for(...).reduce/present composition the
        scatter-gather plan runs (ReduceAggregateExec.compose +
        AggregatePresenter), inlined so this node ALWAYS returns
        presented batches whatever rung served."""
        from filodb_tpu.query.aggregators import aggregator_for
        parts = [b for b in batches if isinstance(b, AggPartialBatch)]
        out = [b for b in batches if not isinstance(b, AggPartialBatch)]
        if parts:
            agg = aggregator_for(self.operator)
            out.append(self._apply_phi(agg.present(agg.reduce(parts))))
        return out

    def _apply_phi(self, pb):
        """The host form of the fused quantile epilogue: identical math
        to InstantVectorFunctionMapper's HISTOGRAM_QUANTILE branch, so
        the fallback rungs stay bit-equal to the fused answer."""
        if self.hist_phi is None or getattr(pb, "hist", None) is None:
            return pb
        import jax.numpy as jnp

        from filodb_tpu.ops import histogram_ops
        from filodb_tpu.query.model import PeriodicBatch
        vals = np.asarray(histogram_ops.hist_quantile(
            jnp.asarray(pb.bucket_tops), jnp.asarray(pb.hist),
            self.hist_phi))
        return PeriodicBatch(pb.keys, pb.steps, vals)


class EventTopKExec(MeshAggregateExec):
    """ExecPlan surface for the event-topK family (the PR 19
    ``event_topk_grid_packed`` exec follow-up): the k hottest GROUPS
    per step, ranked by their aggregated (summed) event value — unlike
    topk(), which selects series WITHIN each group.

    Fused path: meshgrid.serve_event_topk — grouped sums are additive,
    so the cross-shard merge psums the group planes over the mesh FIRST
    and ONE on-device lax.top_k then selects per step (exact, where a
    merge of per-shard topK lists is not), one dispatch and one [T, k]
    readback.  Fallback (breaker / stale topology / non-resident
    shapes): per-shard scatter-gather sum partials reduce host-side and
    the same selection runs in numpy with matching tie semantics
    (stable descending argsort = lax.top_k's lower-index-first)."""

    def __init__(self, dataset: str, shards: Sequence[int],
                 filters: Sequence[ColumnFilter], scan_start_ms: int,
                 scan_end_ms: int, start_ms: int, step_ms: int,
                 end_ms: int, k: int, window_ms: Optional[int] = None,
                 function: Optional[RangeFunctionId] = None,
                 function_args: tuple = (), offset_ms: int = 0,
                 by: tuple = (), without: tuple = (),
                 largest: bool = True, stale_ms: int = 300_000,
                 query_context: Optional[QueryContext] = None,
                 engine=None, mapper=None,
                 planned_generation: Optional[int] = None):
        super().__init__(dataset, shards, filters, scan_start_ms,
                         scan_end_ms, start_ms, step_ms, end_ms,
                         AggregationOperator.SUM, window_ms=window_ms,
                         function=function, function_args=function_args,
                         offset_ms=offset_ms, by=by, without=without,
                         params=(), stale_ms=stale_ms,
                         query_context=query_context, engine=engine,
                         mapper=mapper,
                         planned_generation=planned_generation)
        self.k = int(k)
        self.largest = bool(largest)

    def _args_str(self):
        return (super()._args_str()
                + f", k={self.k}, largest={self.largest}")

    def _serve(self, ctx: ExecContext) -> list:
        from filodb_tpu.parallel import meshgrid
        from filodb_tpu.utils.devicewatch import FLIGHT

        stale = self._topology_stale()
        if stale is None and not FABRIC_BREAKER["open"]:
            try:
                got = self._fused_topk(ctx)
            except Exception as e:
                trip_fabric_breaker(e)
                got = None
            if got is not None:
                return got
        else:
            reason = stale or "breaker"
            meshgrid._fallback(reason)
            FLIGHT.record("mesh.fallback", dataset=self.dataset,
                          reason=reason, shards=len(self.shards))
        return self._select_host(self._per_shard_fallback(ctx))

    def _fused_topk(self, ctx: ExecContext) -> Optional[list]:
        from filodb_tpu.parallel import meshgrid
        from filodb_tpu.query.model import PeriodicBatch
        from filodb_tpu.utils.devicewatch import FLIGHT

        got = self._collect_plans(ctx)
        if got is None:
            return None
        engine, plans, union, report = got
        if not plans:
            return []
        served = meshgrid.serve_event_topk(engine, plans, len(union),
                                           self.k, largest=self.largest)
        FLIGHT.record("mesh.event_topk", dataset=self.dataset,
                      shards=len(plans), groups=len(union), k=self.k,
                      served=served is not None)
        if served is None:
            return None
        vals, gidx = served                       # [T, k] each
        keys = [dict(key) for key in union]
        out = np.full((len(keys), report.num_steps), np.nan)
        tt = np.repeat(np.arange(gidx.shape[0]), gidx.shape[1])
        gg, vv = gidx.ravel(), vals.ravel()
        m = gg >= 0
        out[gg[m], tt[m]] = vv[m]
        # every group keeps its row (NaN where never selected): stable
        # result shape whatever the per-step winners are
        return [PeriodicBatch(keys, report, out)]

    def _select_host(self, batches: list) -> list:
        """Scatter-gather rung: reduce per-shard sum partials, then the
        numpy twin of the on-device selection."""
        from filodb_tpu.query.aggregators import aggregator_for
        from filodb_tpu.query.model import PeriodicBatch
        parts = [b for b in batches if isinstance(b, AggPartialBatch)]
        if not parts:
            return [b for b in batches
                    if not isinstance(b, AggPartialBatch)]
        agg = aggregator_for(AggregationOperator.SUM)
        p = agg.reduce(parts)
        s = np.asarray(p.state["sum"], dtype=np.float64)
        n = np.asarray(p.state["count"], dtype=np.float64)
        sign = 1.0 if self.largest else -1.0
        work = np.where(n > 0, s * sign, -np.inf)          # [G, T]
        kk = min(self.k, work.shape[0])
        if kk < 1:
            return []
        # stable descending argsort ranks ties lower-index-first —
        # the same order lax.top_k resolves them in the fused program
        order = np.argsort(-work, axis=0, kind="stable")[:kk]   # [k, T]
        vals = np.take_along_axis(work, order, axis=0)          # [k, T]
        out = np.full_like(work, np.nan)
        tt = np.tile(np.arange(work.shape[1]), (kk, 1))
        m = np.isfinite(vals)
        out[order[m], tt[m]] = vals[m] * sign
        return [PeriodicBatch(list(p.group_keys), p.steps, out)]
