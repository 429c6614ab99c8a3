"""TimeSeriesShard: per-shard ingestion state machine + scan surface.

The heart of ingestion, matching the reference's TimeSeriesShard
(reference: core/src/main/scala/filodb.core/memstore/TimeSeriesShard.scala:222):

- partition registry: partkey -> part_id -> TimeSeriesPartition (:243,316)
- tag index lookups (:255, PartKeyLuceneIndex)
- flush **groups**: hash(partKey) % groups_per_shard, per-group recovery
  watermarks that skip already-persisted records (:155-157, :390, :488-522)
- flush pipeline: freeze buffers -> write chunks -> write dirty partkeys ->
  index end-time updates -> checkpoint (doFlushSteps :884-974)
- eviction by oldest end-time + bloom filter of evicted keys (:1308-1401)
- ``lookup_partitions`` -> PartLookupResult (:1441-1488)

Single-writer discipline: ``ingest`` must be called from one thread per
shard (the reference's ingestSched); reads take snapshots.  The TPU twist is
the scan surface: ``scan_batch`` materializes matching partitions into one
padded device-ready ChunkBatch instead of per-row iterators.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Iterable, Optional, Sequence

import numpy as np

from filodb_tpu.core.chunk import ChunkBatch, build_batch
from filodb_tpu.core.filters import ColumnFilter
from filodb_tpu.core.record import (IngestRecord, decode_container,
                                    parse_partkey)
from filodb_tpu.core.schemas import ColumnType, Schemas
from filodb_tpu.core.storeconfig import StoreConfig
from filodb_tpu.memstore.index import PartKeyIndex
from filodb_tpu.memstore.partition import TimeSeriesPartition, append_newer
from filodb_tpu.native.ingestfast import HistColumn
from filodb_tpu.store.columnstore import ColumnStore, NullColumnStore, PartKeyRecord
from filodb_tpu.store.metastore import InMemoryMetaStore, MetaStore
from filodb_tpu.utils.bloom import BloomFilter
from filodb_tpu.utils.costmemo import CostMemo
from filodb_tpu.utils.observability import TRACER
from filodb_tpu.workload.quota import SeriesQuotaExceeded


class SplitFiltered(Exception):
    """A record's series belongs to the other half of a shard split
    (ISSUE 13): the ingest path drops it here, counted — never an
    error.  Raised only from the NEW-series path, so established series
    of the retained half pay zero overhead."""

    def __init__(self, n_rows: int = 1):
        self.n_rows = n_rows


_FLUSH_METRICS = None
_SERIES_TOTAL = None

# data columns whose write buffers take a row as a scalar (the bulk path)
_BULK_COLUMNS = (ColumnType.DOUBLE, ColumnType.LONG, ColumnType.TIMESTAMP,
                 ColumnType.INT)


def _flush_m() -> dict:
    """The filodb_flush_* metric objects, resolved once per process."""
    global _FLUSH_METRICS
    if _FLUSH_METRICS is None:
        from filodb_tpu.utils.observability import flush_metrics
        _FLUSH_METRICS = flush_metrics()
    return _FLUSH_METRICS


def _series_total():
    """``filodb_ingest_series_total``, resolved once per process."""
    global _SERIES_TOTAL
    if _SERIES_TOTAL is None:
        from filodb_tpu.utils.observability import ingest_metrics
        _SERIES_TOTAL = ingest_metrics()["series"]
    return _SERIES_TOTAL


@dataclasses.dataclass
class PartLookupResult:
    """Outcome of an index lookup (reference: PartLookupResult,
    TimeSeriesShard.scala:1441-1488): in-memory part ids plus partkeys that
    need on-demand paging from the column store."""

    shard: int
    part_ids: np.ndarray
    missing_partkeys: list[bytes]
    first_schema_hash: Optional[int]


@dataclasses.dataclass
class FlushTask:
    """Snapshot handed from the ingest thread to the flush executor
    (reference: FlushGroup, TimeSeriesShard.scala:110-160)."""

    group: int
    parts: list
    dirty: set
    offset: int
    ingestion_time: int


@dataclasses.dataclass
class ShardStats:
    """Counter bundle (reference: TimeSeriesShardStats, :37-108)."""

    rows_ingested: int = 0
    rows_skipped: int = 0
    out_of_order_dropped: int = 0
    partitions_created: int = 0
    partitions_evicted: int = 0
    partitions_purged: int = 0
    chunks_flushed: int = 0
    flushes_done: int = 0
    # integrity subsystem (filodb_tpu/integrity): decode/checksum
    # corruption detected while serving this shard, and how many of
    # those chunks entered quarantine here
    chunks_corrupt: int = 0
    chunks_quarantined: int = 0
    # workload subsystem (filodb_tpu/workload): new series rejected
    # because their tenant hit its active-series quota, and the rows
    # those rejections dropped
    series_quota_rejected: int = 0
    rows_quota_dropped: int = 0
    # elastic resharding (ISSUE 13): rows skipped because their series
    # hashes to the OTHER half of a split — a child replaying its
    # parent's full partition keeps only its half, and a retired parent
    # refuses to re-materialize series its child now owns
    rows_split_filtered: int = 0


class TimeSeriesShard:
    def __init__(self, dataset: str, schemas: Schemas, shard_num: int,
                 config: Optional[StoreConfig] = None,
                 column_store: Optional[ColumnStore] = None,
                 meta_store: Optional[MetaStore] = None):
        self.dataset = dataset
        self.schemas = schemas
        self.shard_num = shard_num
        self.config = config or StoreConfig()
        self.store = column_store or NullColumnStore()
        self.meta = meta_store or InMemoryMetaStore()
        self.index = PartKeyIndex()
        # lookup results by (filters, range, index state), each priced by
        # the ids it holds: a workspace-wide result (a Python walk over
        # every id to make again) outlives the namespaces' turnover
        self._lookup_cache = CostMemo(64)
        # bumped whenever a partition leaves the in-memory map (evict /
        # purge): lets the device grid cache skip re-validating every
        # requested pid per query (20k dict walks otherwise dominate
        # host-side serving time at high cardinality)
        self.removal_epoch = 0
        # serializes removal_epoch bumps: evictions fire from ingest,
        # housekeeping, AND (on ODP shards) query threads concurrently; a
        # lost read-modify-write would leave stale grid preps "current"
        self._epoch_lock = threading.Lock()
        self.partitions: dict[int, TimeSeriesPartition] = {}
        self.part_set: dict[bytes, int] = {}
        # part id -> 16-bit schema hash; covers index-only (evicted /
        # recovered) entries so lookups can stay schema-consistent without
        # materializing the partition
        self.part_schema_hash: dict[int, int] = {}
        self._next_part_id = 0
        self.num_groups = self.config.groups_per_shard
        # per-group recovery watermarks: records at offset <= watermark were
        # already persisted pre-restart and are skipped during recovery
        self.group_watermarks = [-1] * self.num_groups
        self._dirty_partkeys: list[set[int]] = [set() for _ in range(self.num_groups)]
        # guards the dirty-set swap (flush prepare), merge-back (failed
        # flush), and ingest-side adds against each other
        self._dirty_lock = threading.Lock()
        self.latest_offset = -1
        # series the last batch ingested by the per-series path, and by
        # the bulk path (``_ingest_bulk``)
        self._batch_series = self._batch_bulk = 0
        # newest sample timestamp seen: drives time-boundary flush
        # scheduling (reference: createFlushTasks time boundaries :804-846)
        self.latest_ingest_ts = -1
        self.evicted_keys = BloomFilter(self.config.evicted_pk_bloom_filter_capacity)
        self.stats = ShardStats()
        # set when an eviction/reclaim bookkeeping invariant broke: the
        # shard FAILS further scans rather than serve stale buffers
        # (the reference kills the process on its reclaim meta check)
        self.integrity_failed: Optional[str] = None
        # store-level corruption detections route back here by identity
        from filodb_tpu import integrity
        integrity.register_shard(self)
        self.ingest_sched_check = None  # optional thread-name assertion hook
        # device-resident chunk grids (HBM arena; memstore/devicestore.py),
        # one per (schema, value column); created lazily on first grid scan
        self.device_caches: dict = {}
        # mesh placement: when set (a jax Device), this shard's grid
        # blocks live on THAT device so the SPMD mesh serving path
        # (parallel/meshgrid.py) reads them in place — the multi-device
        # analog of BlockManager-resident serving
        self.grid_device = None
        # shape agreement with the dataset's other local shards
        # (memstore/gridshapes.py; the memstore sets it at setup)
        self.grid_shapes = None
        # monotone counter the device caches key their plan memos by:
        # bumped whenever new rows or chunks could change query results
        self.ingest_epoch = 0
        # counts chunk FREEZES only (a strict subset of ingest_epoch
        # bumps): the encoded chunk set changes exactly on freeze or
        # removal, so the result cache's span table keys on these
        self.freeze_epoch = 0
        self._span_table: Optional[tuple] = None
        self._mutable_floor: Optional[tuple] = None  # (ingest_epoch, ts)
        # flush-time downsampling (reference: ShardDownsampler invoked from
        # doFlushSteps :915-917); set via enable_downsampling()
        self.downsample_publisher = None
        self.downsample_resolutions: tuple[int, ...] = ()
        self._downsamplers: dict[int, object] = {}
        # live rollup subsystem (filodb_tpu/rollup): called after each
        # successful flush with {schema_hash: [(tags, chunkset)]} + the
        # flush ingestion time — the incremental chunk feed the
        # RollupEngine tiers from.  Must never fail the flush.
        self.rollup_listener = None
        # active-series cardinality quota (workload/quota.py): consulted
        # right before a NEW part id is assigned; an over-quota tenant's
        # new series is rejected (rows dropped + counted) while existing
        # series keep ingesting (reference: CardinalityManager/QuotaSource)
        self.series_quota = None
        # data-plane cardinality explorer (ISSUE 6, memstore/cardinality):
        # O(1) churn notes at part-id assignment and evict/purge, plus
        # set_fn-sampled active-series gauges off this shard's index
        from filodb_tpu.memstore.cardinality import CardinalityTracker
        self.cardinality = CardinalityTracker(dataset, shard_num)
        self.cardinality.attach_index(self.index)
        # the FlushScheduler currently driving this shard (node.py /
        # ingest_stream attach it) so the watermark ledger can surface
        # flush-queue depth/age in /admin/shards
        self.flush_scheduler = None
        # elastic resharding (ISSUE 13, coordinator/split.py):
        # - split_ingest_filter: tags -> keep?  Installed on split
        #   CHILDREN (each keeps its half of the parent's hash space
        #   while replaying the parent's partition) and on retired
        #   parents (refuse to re-materialize migrated series).  Checked
        #   only on the new-series path — established retained series
        #   never pay it.
        # - _reshard_memo: pid -> post-split shard, the scan-exclusion
        #   memo filter_resharded() uses between cutover and retire.
        self.split_ingest_filter = None
        self._reshard_memo: dict[int, int] = {}
        self._reshard_memo_key: Optional[tuple] = None
        # serializes split clone/backfill against the flush executor so
        # the (persisted chunks, checkpoints) pair a child inherits is a
        # consistent at-rest snapshot (chunks persist BEFORE checkpoints
        # advance; cloning between the two would double or drop rows)
        self.split_clone_lock = threading.Lock()

    def enable_downsampling(self, publisher, resolutions_ms) -> None:
        self.downsample_publisher = publisher
        self.downsample_resolutions = tuple(resolutions_ms)
        self._downsamplers = {}

    def close(self) -> None:
        """Release registry-held callbacks (Gauge.remove contract):
        everything this shard registered against process-wide state must
        be unwound or the registry keeps the shard alive and keeps
        exporting rows for it.  Subclasses extend (ODP deregisters its
        page-cache pool)."""
        self.cardinality.close()

    # ------------------------------------------------------------------ ingest

    def ingest_container(self, container: bytes, offset: int) -> int:
        """One container, whole: decode, the appends (its common series
        in bulk, the rest a series at a time), the device grids' open
        blocks, the epoch bump (stage ``ingest.container``, on the shard's
        ingest thread; ``filodb_ingest_series_total`` counts the series by
        the path they took)."""
        self._batch_bulk = self._batch_series = 0
        with TRACER.stage("ingest.container", cpu=True) as sp:
            added = self._ingest_container_fast(container, offset)
            if added is None:
                added = self.ingest(
                    decode_container(container, self.schemas), offset)
            sp.tag(samples=added, bulk=self._batch_bulk,
                   series=self._batch_series)
        series = _series_total()
        for path, n in (("bulk", self._batch_bulk),
                        ("series", self._batch_series)):
            if n:
                series.inc(n, dataset=self.dataset, shard=self.shard_num,
                           path=path)
        return added

    def _ingest_container_fast(self, container: bytes, offset: int
                               ) -> Optional[int]:
        """Columnar ingest: C++ container decode (native/ingestfast.py),
        the common series appended in bulk (:meth:`_ingest_bulk`), the
        rest a series at a time.  Histogram columns arrive blob-expanded
        (HistColumn) and batch-append when a series' rows share one
        bucket scheme and width — the rare mixed-scheme run falls back
        to per-record ingest for just that series.  Returns None when
        this container can't take the fast path (string columns, mixed
        schemas, no compiler) — the caller then runs the per-record
        path.  Semantics match :meth:`ingest` exactly;
        tests/test_memstore.py proves equivalence on out-of-order and
        watermark-skip data."""
        from filodb_tpu.native import ingestfast

        dec = ingestfast.decode(container, self.schemas)
        if dec is None:
            return None
        if self.ingest_sched_check is not None:
            self.ingest_sched_check()
        if dec.num_records == 0:
            self.latest_offset = max(self.latest_offset, offset)
            return 0
        schema = self.schemas.by_hash(dec.schema_hash)
        ts, cols, uniq_idx = dec.ts, dec.cols, dec.uniq_idx
        groups_r = (dec.part_hashes % np.uint32(self.num_groups)).astype(
            np.int64)
        # recovery watermark skip (reference IngestConsumer :488-522);
        # steady state short-circuits on max(watermarks) < offset
        if offset <= max(self.group_watermarks):
            keep = offset > np.asarray(self.group_watermarks)[groups_r]
            skipped = int((~keep).sum())
            if skipped:
                self.stats.rows_skipped += skipped
                ts, uniq_idx = ts[keep], uniq_idx[keep]
                cols = [c[keep] for c in cols]
        n_uniq = len(dec.partkeys)
        if n_uniq == len(ts) == dec.num_records:
            # a record a series and none skipped (a scrape's shape): the
            # records are the series in order, a row each
            ts_s, cols_s = ts, cols
            n_l, at_l = [1] * n_uniq, list(range(n_uniq + 1))
        else:
            order = np.argsort(uniq_idx, kind="stable")
            ts_s = ts[order]
            cols_s = [c[order] for c in cols]
            counts = np.bincount(uniq_idx, minlength=n_uniq)
            n_l, at_l = counts.tolist(), [0] + np.cumsum(counts).tolist()
        maxint = np.iinfo(np.int64).max
        try:
            rest, added_total = self._ingest_bulk(
                dec, schema, ts_s, cols_s, n_l, at_l, groups_r)
            self._batch_series = len(rest)
            for u in rest:
                s0, s1 = at_l[u], at_l[u + 1]
                first = int(dec.uniq_first[u])
                try:
                    part = self._get_or_add_partition_pk(
                        dec.partkeys[u], schema, int(dec.part_hashes[first]),
                        int(ts_s[s0]))
                except SeriesQuotaExceeded:
                    # over-quota NEW series: its rows drop, the rest of the
                    # container keeps ingesting (existing series unaffected)
                    self.stats.rows_quota_dropped += s1 - s0
                    self.series_quota.note_dropped_samples(
                        parse_partkey(dec.partkeys[u]), s1 - s0)
                    continue
                except SplitFiltered:
                    # the series belongs to the other half of a split: a
                    # child keeps only its half of the replayed parent
                    # partition (ISSUE 13)
                    self.stats.rows_split_filtered += s1 - s0
                    continue
                added, dropped = self._ingest_series_block(
                    part, ts_s[s0:s1], [c[s0:s1] for c in cols_s])
                added_total += added
                self.stats.rows_ingested += added
                self.stats.out_of_order_dropped += dropped
                if self.index.end_time(part.part_id) != maxint:
                    self.index.mark_active(part.part_id)
                with self._dirty_lock:
                    self._dirty_partkeys[int(groups_r[first])].add(
                        part.part_id)
        except BaseException:
            # a batch that raises midway may have moved write buffers:
            # what is cached per ingest epoch (the device grid's plans,
            # mutable_floor) must not outlive that; the rows that did land
            # reach the grid's open blocks before the bump, as a whole
            # batch's do (the ingest thread alone appends)
            self._flush_grid_appends()
            self.ingest_epoch += 1
            raise
        if len(ts):
            self.latest_ingest_ts = max(self.latest_ingest_ts,
                                        int(ts.max()))
        self.latest_offset = max(self.latest_offset, offset)
        if added_total:
            self._flush_grid_appends()
            self.ingest_epoch += 1
        return added_total

    def _ingest_bulk(self, dec, schema, ts_s: np.ndarray, cols_s: list,
                     n_l: list, at_l: list, groups_r: np.ndarray
                     ) -> tuple[list, int]:
        """The container's common series, a container at a time: those
        whose partition is resident and plain (a traced one logs its
        rows), whose rows are in order, newer than its high-water mark
        and fit its write buffer (``partition.append_newer``).  What the
        per-series path does a series is done here once: the stats, the
        dirty sets (one ``update`` a flush group), the index's active
        marks (only ids whose end time is closed), and ONE call a device
        grid with the rows as arrays.  Series ``u`` has ``n_l[u]`` rows
        from ``at_l[u]`` of ``ts_s`` / ``cols_s``.  Returns (the series
        left to the per-series path, the rows added): every series with
        rows that it did not take (new, re-paged, traced, out of order, a
        histogram, a buffer that must freeze) is ingested there as before.
        Python lists, not arrays, a series: a NumPy call over a
        container's rows lets the interpreter go, and the ingest thread
        waits to have it back behind every request thread."""
        # the series with rows (the watermark may have skipped all of one's)
        rest = [u for u, k in enumerate(n_l) if k]
        if any(c.ctype not in _BULK_COLUMNS
               for c in schema.data.columns[1:]):
            return rest, 0
        parts = list(map(self.partitions.get,
                         map(self.part_set.get, dec.partkeys)))
        cand = [u for u in rest if type(parts[u]) is TimeSeriesPartition
                and parts[u].schema is schema]
        if len(ts_s) > len(rest) and cand:
            # a series whose rows are out of order among themselves drops
            # some: ingest_block's
            starts = np.asarray(at_l)
            rows = np.flatnonzero(np.diff(ts_s) <= 0) + 1
            rows = rows[~np.isin(rows, starts)]
            if len(rows):
                bad = set((np.searchsorted(starts, rows, "right") - 1)
                          .tolist())
                cand = [u for u in cand if u not in bad]
        if not cand:
            return rest, 0
        empty = append_newer([parts[u] for u in cand],
                             [at_l[u] for u in cand],
                             [n_l[u] for u in cand], ts_s, cols_s)
        # what was taken: the series, their ids by flush group, and the
        # bulk rows that are the first of a buffer that held none
        got, pids, opened, added = [], [], [], 0
        groups, first = groups_r.tolist(), dec.uniq_first.tolist()
        by_group: dict = {}
        for u, e in zip(cand, empty):
            if e is None:
                continue
            pid = parts[u].part_id
            got.append(u)
            pids.append(pid)
            by_group.setdefault(groups[first[u]], []).append(pid)
            if e:
                opened.append(added)
            added += n_l[u]
        if not got:
            return rest, 0
        if len(got) < len(rest):
            taken = set(got)
            rest = [u for u in rest if u not in taken]
        else:
            rest = []
        self._batch_bulk = len(got)
        self.stats.rows_ingested += added
        for pid in self.index.closed(pids):
            self.index.mark_active(pid)
        with self._dirty_lock:
            for g, ids in by_group.items():
                self._dirty_partkeys[g].update(ids)
        if self.device_caches:
            if added == len(ts_s):
                # every row of the container, in order
                ts_b, cols_b = ts_s, cols_s
            else:
                rows = np.fromiter(itertools.chain.from_iterable(
                    range(at_l[u], at_l[u + 1]) for u in got), np.int64,
                    added)
                ts_b, cols_b = ts_s[rows], [c[rows] for c in cols_s]
            pid_rows = pids if added == len(got) else [
                pid for u, pid in zip(got, pids) for _ in range(n_l[u])]
            # each grid of the schema told once (a snapshot: a query's
            # thread may add a cache meanwhile)
            for (shash, cid), cache in tuple(self.device_caches.items()):
                if shash == schema.schema_hash:
                    cache.note_append_rows(pid_rows, ts_b, cols_b[cid - 1],
                                           opened)
        return rest, added

    @staticmethod
    def _ingest_series_block(part, ts: np.ndarray, cols: list
                             ) -> tuple[int, int]:
        """Batch-append one series' rows.  HistColumn entries become
        (bucket scheme, counts matrix) pairs when the run is uniform
        (one scheme, one width — the overwhelmingly common case);
        otherwise the run ingests per record so bucket-scheme-switch
        semantics (buffer freeze) match the slow path exactly."""
        block_cols: list = []
        uniform = True
        for c in cols:
            if not isinstance(c, HistColumn):
                block_cols.append(c)
                continue
            if len(c.schemes) > 1 and \
                    (c.scheme_idx != c.scheme_idx[0]).any():
                uniform = False
                break
            nb0 = int(c.nbuckets[0])
            if (c.nbuckets != nb0).any():
                uniform = False
                break
            block_cols.append((c.schemes[int(c.scheme_idx[0])],
                               c.counts[:, :nb0]))
        if uniform:
            return part.ingest_block(ts, block_cols)
        added = dropped = 0
        for i in range(len(ts)):
            # .copy(): a buffered row view would pin the whole container
            # counts matrix until the buffer freezes
            row = [(c.schemes[int(c.scheme_idx[i])],
                    c.counts[i, :int(c.nbuckets[i])].copy())
                   if isinstance(c, HistColumn) else c[i] for c in cols]
            if part.ingest(int(ts[i]), row):
                added += 1
            else:
                dropped += 1
        return added, dropped

    def ingest(self, records: Iterable[IngestRecord], offset: int) -> int:
        """Ingest a batch of records at a stream offset.  Returns rows added.

        Group watermark skipping mirrors the reference's IngestConsumer
        (:488-522): during recovery, a record whose flush group checkpointed
        beyond ``offset`` is already persisted — skip it.
        """
        if self.ingest_sched_check is not None:
            self.ingest_sched_check()
        n = 0
        touched = set()
        try:
            for rec in records:
                group = rec.part_hash % self.num_groups
                if offset <= self.group_watermarks[group]:
                    self.stats.rows_skipped += 1
                    continue
                try:
                    part = self._get_or_add_partition(rec)
                except SeriesQuotaExceeded:
                    self.stats.rows_quota_dropped += 1
                    self.series_quota.note_dropped_samples(rec.tags)
                    continue
                except SplitFiltered:
                    self.stats.rows_split_filtered += 1
                    continue
                touched.add(part.part_id)
                if part.ingest(rec.timestamp, rec.values):
                    n += 1
                    self.stats.rows_ingested += 1
                else:
                    self.stats.out_of_order_dropped += 1
                if self.index.end_time(part.part_id) != np.iinfo(np.int64).max:
                    self.index.mark_active(part.part_id)
                with self._dirty_lock:
                    self._dirty_partkeys[group].add(part.part_id)
                if rec.timestamp > self.latest_ingest_ts:
                    self.latest_ingest_ts = rec.timestamp
        except BaseException:
            self._flush_grid_appends()
            self.ingest_epoch += 1      # rows may have landed: see above
            raise
        self.latest_offset = max(self.latest_offset, offset)
        self._batch_series = len(touched)
        if n:
            self._flush_grid_appends()
            self.ingest_epoch += 1
        return n

    def _get_or_add_partition(self, rec: IngestRecord) -> TimeSeriesPartition:
        return self._get_or_add_partition_pk(
            rec.partkey(), self.schemas.by_hash(rec.schema_hash),
            rec.part_hash, rec.timestamp, tags=rec.tags)

    def _get_or_add_partition_pk(self, pk: bytes, schema, part_hash: int,
                                 timestamp: int, tags: Optional[dict] = None
                                 ) -> TimeSeriesPartition:
        """Partition registry lookup/creation keyed by raw partkey bytes;
        tags are parsed lazily so the columnar fast path never builds a
        tag dict for known series (reference: partSet O(1) lookup by
        ingest record, TimeSeriesShard.scala:1091)."""
        pid = self.part_set.get(pk)
        if pid is not None:
            part = self.partitions.get(pid)
            if part is not None:
                return part
            # index-only entry (recovered or paged-out): re-materialize the
            # partition under its existing part id, keeping index lifecycle
            rtags = tags if tags is not None else parse_partkey(pk)
            part = self._partition_cls(rtags)(
                pid, schema, pk, rtags, part_hash % self.num_groups,
                capacity=self.config.max_chunks_size)
            part.on_freeze = self._on_chunk_freeze
            part.on_corrupt = self.note_corrupt_chunk
            part.on_append = self._on_rows_appended
            self.partitions[pid] = part
            self.index.mark_active(pid)
            return part
        # evicted-key bloom check: a maybe-evicted key re-reads its true
        # start time from the column store lifecycle (reference :1103-1122)
        if tags is None:
            tags = parse_partkey(pk)
        if self.split_ingest_filter is not None \
                and not self.split_ingest_filter(tags):
            raise SplitFiltered()
        if self.series_quota is not None \
                and not self.series_quota.allow_new_series(
                    tags, shard=self.shard_num):
            self.stats.series_quota_rejected += 1
            tenant = self.series_quota.tenant_of(tags)
            raise SeriesQuotaExceeded(
                tenant, self.series_quota.active(tenant),
                self.series_quota.limit_for(tenant) or 0)
        start_time = timestamp
        pid = self._next_part_id
        self._next_part_id += 1
        group = part_hash % self.num_groups
        part = self._partition_cls(tags)(
            pid, schema, pk, tags, group,
            capacity=self.config.max_chunks_size)
        part.on_freeze = self._on_chunk_freeze
        part.on_corrupt = self.note_corrupt_chunk
        part.on_append = self._on_rows_appended
        self.partitions[pid] = part
        self.part_set[pk] = pid
        self.part_schema_hash[pid] = schema.schema_hash
        self.index.add_partkey(pid, pk, tags, start_time)
        self.stats.partitions_created += 1
        self.cardinality.note_created()
        return part

    def _partition_cls(self, tags: dict[str, str]):
        """TracingTimeSeriesPartition for series matching the
        `trace-filters` tag subset (reference: TimeSeriesPartition.scala:451
        TracingTimeSeriesPartition); the normal class otherwise."""
        tf = self.config.trace_filters
        if tf and all(tags.get(k) == str(v) for k, v in tf.items()):
            from filodb_tpu.memstore.partition import \
                TracingTimeSeriesPartition
            return TracingTimeSeriesPartition
        return TimeSeriesPartition

    def create_partition(self, schema_name: str, tags: dict[str, str],
                         start_time: int) -> TimeSeriesPartition:
        """Direct partition creation for tests/recovery paths."""
        from filodb_tpu.core.record import canonical_partkey, partition_hash
        rec = IngestRecord(self.schemas[schema_name].schema_hash, tags,
                           start_time, (), 0, partition_hash(tags))
        return self._get_or_add_partition(rec)

    # ------------------------------------------------------------------ flush

    def prepare_flush_group(self, group: int,
                            ingestion_time: Optional[int] = None
                            ) -> "FlushTask":
        """Ingest-thread half of a pipelined flush: O(partitions-in-group)
        buffer detaches plus state snapshots; no encoding, no IO
        (reference: prepareFlushGroup, TimeSeriesShard.scala:756-774).
        The returned task runs on a flush executor via
        :meth:`run_flush_task`; tasks for the SAME group must run in
        submission order (the scheduler serializes per group)."""
        itime = ingestion_time if ingestion_time is not None \
            else int(time.time() * 1000)
        parts = [p for p in self.partitions.values() if p.group == group]
        for part in parts:
            part.freeze_raw()
        with self._dirty_lock:
            dirty = self._dirty_partkeys[group]
            self._dirty_partkeys[group] = set()
        return FlushTask(group=group, parts=parts, dirty=dirty,
                         offset=self.latest_offset, ingestion_time=itime)

    def run_flush_task(self, task: "FlushTask") -> int:
        """Flush-executor half: encode pending buffers (frozen at prepare
        time — never the live write buffer), write chunks, downsample,
        persist partkeys, checkpoint (the doFlushSteps pipeline,
        reference :884-974).  Returns chunksets written.  On failure the
        dirty partkeys are re-queued so a later flush persists them.

        Instrumented per ISSUE 2 (reference: Kamon spans around flush,
        TimeSeriesShard.scala:888-891): one span + the filodb_flush_*
        metrics per task; failures count before re-raising."""
        m = _flush_m()
        t0 = time.perf_counter()
        try:
            with TRACER.span("memstore.flush", dataset=self.dataset,
                             shard=self.shard_num, group=task.group):
                n = self._run_flush_task(task)
        except BaseException:
            m["failures"].inc(dataset=self.dataset)
            raise
        finally:
            m["flush_seconds"].observe(time.perf_counter() - t0,
                                       dataset=self.dataset)
        m["chunks"].inc(n, dataset=self.dataset)
        from filodb_tpu.utils.devicewatch import FLIGHT
        FLIGHT.record("flush", dataset=self.dataset, shard=self.shard_num,
                      group=task.group, chunks=n,
                      seconds=round(time.perf_counter() - t0, 6))
        return n

    def _run_flush_task(self, task: "FlushTask") -> int:
        # split_clone_lock scopes the persist->checkpoint pair: a split
        # clone (coordinator/split.py) holding it sees either none or
        # all of one flush task, so the child's inherited (chunks,
        # checkpoints) snapshot keeps the parent's own recovery
        # invariant (checkpoint only covers persisted rows).  The sqlite
        # layer serializes writers anyway, so cross-group flush tasks
        # lose no real concurrency here.
        with self.split_clone_lock:
            return self._run_flush_task_locked(task)

    def _run_flush_task_locked(self, task: "FlushTask") -> int:
        collected: list[tuple] = []  # (part, its fresh chunksets)
        try:
            chunksets = []
            ds_pairs: dict[int, list] = {}  # schema_hash -> [(tags, cs)]
            for part in task.parts:
                fresh = part.collect_flush_chunks()
                if fresh:
                    collected.append((part, fresh))
                chunksets.extend(fresh)
                if fresh and (self.downsample_publisher is not None
                              or self.rollup_listener is not None):
                    ds_pairs.setdefault(part.schema.schema_hash, []).extend(
                        (part.tags, cs) for cs in fresh)
            if chunksets:
                self.store.write_chunks(self.dataset, self.shard_num,
                                        chunksets, task.ingestion_time)
            if self.downsample_publisher is not None:
                for shash, pairs in ds_pairs.items():
                    self._downsampler_for(shash).downsample_chunksets(pairs)
            if task.dirty:
                recs = [PartKeyRecord(self.index.partkey(pid),
                                      self.index.start_time(pid),
                                      self.index.end_time(pid),
                                      self.shard_num,
                                      self.partitions[pid].schema.schema_hash)
                        for pid in task.dirty if pid in self.partitions]
                self.store.write_part_keys(self.dataset, self.shard_num, recs)
        except BaseException:
            # nothing persisted for sure: requeue both the chunksets and
            # the dirty partkeys so the next flush retries them (store
            # writes are idempotent by chunk id / partkey upsert)
            for part, fresh in collected:
                part.requeue_unflushed(fresh)
            with self._dirty_lock:
                self._dirty_partkeys[task.group] |= task.dirty
            raise
        # checkpoint only after chunks+partkeys persisted (reference :949-960)
        self.meta.write_checkpoint(self.dataset, self.shard_num, task.group,
                                   task.offset)
        if self.rollup_listener is not None and ds_pairs:
            # hand the fresh chunksets to the live rollup engine AFTER
            # the flush persisted+checkpointed (the engine's restart
            # catch-up reads the store by ingestion time, so a crash
            # between persist and handoff replays, never loses)
            try:
                self.rollup_listener(ds_pairs, task.ingestion_time)
            except Exception:  # noqa: BLE001 — rollup must never fail a flush
                import traceback
                traceback.print_exc()
        self.group_watermarks[task.group] = max(
            self.group_watermarks[task.group], task.offset)
        self.stats.chunks_flushed += len(chunksets)
        self.stats.flushes_done += 1
        # proactive HBM reclaim off the query path: trim device caches
        # to (1-headroom) of budget while we're already on the flush
        # executor (the reference's background headroom task)
        frac = self.config.device_headroom_frac
        if frac > 0:
            for cache in list(self.device_caches.values()):
                cache.ensure_headroom(frac)
        return len(chunksets)

    def flush_group(self, group: int, ingestion_time: Optional[int] = None) -> int:
        """Synchronous flush of one group (prepare + run inline)."""
        return self.run_flush_task(self.prepare_flush_group(group,
                                                            ingestion_time))

    def _downsampler_for(self, schema_hash: int):
        ds = self._downsamplers.get(schema_hash)
        if ds is None:
            from filodb_tpu.downsample.sharddown import ShardDownsampler
            ds = ShardDownsampler(self.dataset, self.shard_num,
                                  self.schemas.by_hash(schema_hash),
                                  self.downsample_publisher,
                                  self.downsample_resolutions)
            self._downsamplers[schema_hash] = ds  # filolint: disable=bounded-cache — keyed by schema hash, bounded by the configured schema set
        return ds

    def flush_all(self, ingestion_time: Optional[int] = None) -> int:
        return sum(self.flush_group(g, ingestion_time)
                   for g in range(self.num_groups))

    # ------------------------------------------------------------- lifecycle

    def bump_removal_epoch(self) -> None:
        """Atomic removal-epoch increment; see ``_epoch_lock``."""
        with self._epoch_lock:
            self.removal_epoch += 1

    def note_corrupt_chunk(self, err, newly_quarantined: bool) -> None:
        """Partition/store hook: a chunk of this shard failed checksum
        or decode (already quarantined + logged by the integrity
        funnel); keep the per-shard tally the tentpole asks for."""
        self.stats.chunks_corrupt += 1
        if newly_quarantined:
            self.stats.chunks_quarantined += 1
            # grid plans staged from this chunk must revalidate, so the
            # DEVICE serving path excludes the quarantined chunk exactly
            # like the host path's read_range does
            self.bump_removal_epoch()

    def _check_integrity(self) -> None:
        """Hard tripwire: once eviction/reclaim bookkeeping is known
        broken, refuse to serve (stale buffers are worse than errors)."""
        if self.integrity_failed is not None:
            from filodb_tpu.integrity import IntegrityInvariantError
            raise IntegrityInvariantError(
                f"shard {self.shard_num} failed integrity: "
                f"{self.integrity_failed}")

    def evict_partitions(self, n: int) -> int:
        """Evict up to n longest-stopped partitions (reference :1308-1401).
        Their data must already be flushed; in-memory state is dropped and
        the partkey recorded in the evicted bloom filter."""
        victims = self.index.part_ids_ordered_by_end_time(n)
        for pid in victims:
            part = self.partitions.pop(pid, None)
            if part is None:
                continue
            self.bump_removal_epoch()
            self.part_set.pop(part.partkey, None)
            self.evicted_keys.add(part.partkey)
            self.index.remove([pid])
            if self.series_quota is not None:
                self.series_quota.note_removed(part.tags)
            self.stats.partitions_evicted += 1
            self.cardinality.note_removed("evict")
        return len(victims)

    def purge_expired(self, retention_ms: int, now_ms: int) -> int:
        """Drop partitions whose data aged out entirely (reference :776-795)."""
        cutoff = now_ms - retention_ms
        # a snapshot: the ingest thread adds partitions while a purge on
        # another thread waits for one's lock
        doomed = [pid for pid, p in list(self.partitions.items())
                  if p.latest_timestamp < cutoff]
        for pid in doomed:
            part = self.partitions.pop(pid)
            self.bump_removal_epoch()
            self.part_set.pop(part.partkey, None)
            self.index.remove([pid])
            if self.series_quota is not None:
                self.series_quota.note_removed(part.tags)
            self.stats.partitions_purged += 1
            self.cardinality.note_removed("purge")
        return len(doomed)

    # ------------------------------------------------- elastic resharding

    def _resharded_shard(self, pid: int, total: int, spread: int) -> int:
        """The shard this part id's series routes to under a
        ``total``-shard topology, memoized per pid (tags parse + two
        hashes otherwise repeat on every post-cutover scan)."""
        key = (total, spread)
        if self._reshard_memo_key != key:
            self._reshard_memo = {}
            self._reshard_memo_key = key
        got = self._reshard_memo.get(pid)
        if got is None:
            from filodb_tpu.parallel.shardmap import shard_of_tags
            got = self._reshard_memo[pid] = shard_of_tags(  # filolint: disable=bounded-cache — keyed by part id, bounded by this shard's partition registry; dropped whole on (total, spread) change
                self.index.tags(pid), total, spread)
        return got

    def filter_resharded(self, lookup: PartLookupResult, total: int,
                         spread: int) -> PartLookupResult:
        """Scan-time exclusion for a split PARENT between cutover and
        retire (ISSUE 13): drop series that now belong to a child shard
        under the ``total``-shard topology.  The parent keeps a full
        superset of the data until retire purges it (abort stays
        lossless), so every post-cutover scan must slice off the
        migrated half or the child's answers double-count."""
        from filodb_tpu.parallel.shardmap import shard_of_tags
        keep = [pid for pid in lookup.part_ids
                if self._resharded_shard(int(pid), total, spread)
                == self.shard_num]
        missing = [pk for pk in lookup.missing_partkeys
                   if shard_of_tags(parse_partkey(pk), total, spread)
                   == self.shard_num]
        if len(keep) == len(lookup.part_ids) \
                and len(missing) == len(lookup.missing_partkeys):
            return lookup
        return PartLookupResult(lookup.shard,
                                np.asarray(keep, dtype=np.int32), missing,
                                lookup.first_schema_hash)

    def purge_resharded(self, total: int, spread: int) -> list[bytes]:
        """RETIRE a split parent's migrated half: drop every in-memory
        partition (and index entry) whose series now belongs to a child
        shard.  Returns the purged partkeys so the caller can delete
        the persisted copies too.  Runs on the control plane AFTER the
        grace window — the children have been serving this data since
        cutover."""
        doomed = []
        for pid in list(self.partitions):
            if self._resharded_shard(pid, total, spread) != self.shard_num:
                doomed.append(pid)
        from filodb_tpu.parallel.shardmap import shard_of_tags
        # index-only entries (evicted / recovered, no live partition)
        # migrate too — their partkeys still feed lookups and ODP
        for pk, pid in list(self.part_set.items()):
            if pid not in self.partitions \
                    and shard_of_tags(parse_partkey(pk), total,
                                      spread) != self.shard_num:
                doomed.append(pid)
        purged: list[bytes] = []
        for pid in doomed:
            part = self.partitions.pop(pid, None)
            pk = part.partkey if part is not None else self.index.partkey(pid)
            self.bump_removal_epoch()
            self.part_set.pop(pk, None)
            self.part_schema_hash.pop(pid, None)
            self.index.remove([pid])
            if self.series_quota is not None:
                tags = part.tags if part is not None else parse_partkey(pk)
                self.series_quota.note_removed(tags)
            self.stats.partitions_purged += 1
            self.cardinality.note_removed("purge")
            purged.append(pk)
        if purged:
            self._lookup_cache.clear()
        return purged

    def mark_stopped_series(self, now_ms: int, stale_ms: int) -> int:
        """Set index end-times for series that stopped ingesting (reference:
        updateIndexWithEndTime during flush, :1037-1057)."""
        n = 0
        for pid, part in self.partitions.items():
            if part.latest_timestamp < now_ms - stale_ms \
                    and self.index.end_time(pid) == np.iinfo(np.int64).max:
                self.index.update_end_time(pid, part.latest_timestamp)
                n += 1
        return n

    # ------------------------------------------------------------------ query

    def lookup_partitions(self, filters: Sequence[ColumnFilter],
                          start_time: int, end_time: int,
                          limit: Optional[int] = None) -> PartLookupResult:
        """Index lookup restricted to ONE schema — the first matched, like the
        reference's MultiSchemaPartitionsExec runtime schema discovery
        (exec/MultiSchemaPartitionsExec.scala:41-85).  Ids whose partitions
        are not in memory surface as ``missing_partkeys`` for on-demand
        paging.

        Repeated dashboard lookups are cached keyed on (filters, range,
        index version): at 100k+ series the postings walk dominates served
        query latency otherwise."""
        # len(partitions) covers re-materialization of index-only entries
        # (which may not bump the index version); eviction bumps it.
        key = (tuple(filters), start_time, end_time, limit,
               self.index.version, len(self.partitions))
        cached = self._lookup_cache.get(key)
        if cached is not None:
            return cached
        result = self._lookup_partitions_uncached(filters, start_time,
                                                  end_time, limit)
        # handed out by identity to every later request and never
        # mutated: what is derived from it (devicestore's lane
        # resolution, its content fingerprint, the fabric's rows) is
        # kept by that identity, and read-only is how they can tell
        result.part_ids.setflags(write=False)
        self._lookup_cache.put(key, result, len(result.part_ids) + 1)
        return result

    def _lookup_partitions_uncached(self, filters, start_time, end_time,
                                    limit) -> PartLookupResult:
        ids = self.index.part_ids_from_filters(filters, start_time, end_time,
                                               limit)
        first_schema = None
        in_mem: list[int] = []
        missing: list[bytes] = []
        for i in ids:
            pid = int(i)
            part = self.partitions.get(pid)
            if part is None:
                missing.append(self.index.partkey(pid))
                continue
            if first_schema is None:
                first_schema = part.schema.schema_hash
            if part.schema.schema_hash == first_schema:
                in_mem.append(pid)
        return PartLookupResult(self.shard_num, np.asarray(in_mem, dtype=np.int32),
                                missing, first_schema)

    def chunk_span_table(self):
        """Flat ``(pid, chunk_id, start_time, end_time)`` int64 arrays
        over every in-memory partition's encoded chunks — the result
        cache's immutability digest source (query/resultcache.py).
        Cached per (freeze_epoch, removal_epoch, index version,
        partition count): the encoded chunk set changes exactly on
        freeze/removal, so live per-row ingest never rebuilds it."""
        key = (self.freeze_epoch, self.removal_epoch, self.index.version,
               len(self.partitions))
        tbl = self._span_table
        if tbl is not None and tbl[0] == key:
            return tbl[1]
        pid_l: list = []
        cid_l: list = []
        cs_l: list = []
        ce_l: list = []
        for pid, part in list(self.partitions.items()):
            with part._lock:
                for cs in part.chunks:
                    pid_l.append(pid)
                    cid_l.append(cs.info.chunk_id)
                    cs_l.append(cs.info.start_time)
                    ce_l.append(cs.info.end_time)
        arrs = (np.asarray(pid_l, np.int64), np.asarray(cid_l, np.int64),
                np.asarray(cs_l, np.int64), np.asarray(ce_l, np.int64))
        self._span_table = (key, arrs)
        return arrs

    def mutable_floor(self) -> Optional[int]:
        """Earliest mutable (write-buffer / pending-encode) row
        timestamp across ALL partitions, or None when everything is
        encoded — the result cache's closed-segment probe, cached per
        ingest epoch so a burst of queries between ingest batches pays
        one partition walk.  Deliberately filter-independent: an
        unmatched partition's buffer marking a segment open only costs
        a cache miss, never staleness."""
        # capture the epoch BEFORE the walk (chunk_span_table does the
        # same): a row ingested mid-walk bumps the epoch and must force
        # a recompute — caching the walk under the post-bump epoch
        # would hide that row until the NEXT ingest
        epoch = self.ingest_epoch
        mf = self._mutable_floor
        if mf is not None and mf[0] == epoch:
            return mf[1]
        lo: Optional[int] = None
        for part in list(self.partitions.values()):
            mt = part.mutable_floor()
            if mt is not None and (lo is None or mt < lo):
                lo = mt
        self._mutable_floor = (epoch, lo)
        return lo

    def _partition_for_scan(self, part_id: int) -> Optional[TimeSeriesPartition]:
        """Resolve a part id for scanning.  The ODP shard overrides this to
        consult its paged-partition cache as well."""
        return self.partitions.get(part_id)

    def grid_partition(self, part_id: int) -> Optional[TimeSeriesPartition]:
        """Resolve a part id for the DEVICE GRID (devicestore.py block
        builds and plan validation).  The ODP shard overrides this to
        serve PAGED partitions too — paged-in history registers as grid
        blocks, so a repeat dashboard hit over evicted ranges serves at
        device speed (reference: DemandPagedChunkStore.scala:34 pages
        straight into block memory and serves identically)."""
        return self.partitions.get(part_id)

    # --------------------------------------------------- device-resident scan

    def _on_rows_appended(self, part, ts, cols, was_empty: bool) -> None:
        """``partition.on_append``: rows reached ``part``'s write buffer
        (one row's scalars or a block's arrays; ``cols`` the data columns
        after the timestamp).  The device grids of its schema are told,
        O(1) a call; with no grid yet (set-up's load) it costs the one
        test."""
        caches = self.device_caches
        if not caches:
            return
        shash = part.schema.schema_hash
        # a snapshot: a query's thread adds a cache (``device_cache``, the
        # first query of a column) while a container ingests
        for (cache_hash, cid), cache in tuple(caches.items()):
            if cache_hash == shash:
                cache.note_append(part.part_id, ts, cols[cid - 1], was_empty)

    def _flush_grid_appends(self) -> None:
        """The rows this batch brought, into the device grids' open
        blocks, before the epoch bump that makes them readable."""
        for cache in list(self.device_caches.values()):
            cache.flush_appends()

    def _on_chunk_freeze(self, cs) -> None:
        self.ingest_epoch += 1
        self.freeze_epoch += 1
        for (shash, _cid), cache in self.device_caches.items():
            if shash == cs.schema_hash or cs.schema_hash == 0:
                cache.note_freeze(cs)

    def device_cache(self, schema_hash: int, column_id: int,
                     hist: bool = False):
        cache = self.device_caches.get((schema_hash, column_id))
        if cache is None:
            from filodb_tpu.memstore.devicestore import DeviceGridCache
            cache = DeviceGridCache(self, schema_hash, column_id,
                                    self.config.device_cache_bytes,
                                    self.config.grid_step_ms, hist=hist)
            self.device_caches[(schema_hash, column_id)] = cache  # filolint: disable=bounded-cache — keyed by (schema, column); each cache holds its own byte budget
        return cache

    def _grid_cache_for(self, part_ids: Sequence[int],
                        column_id: Optional[int]):
        """Shared grid-eligibility preamble: resolve the value column off
        the first partition, require a DOUBLE or HISTOGRAM column, fetch
        the cache.  The ORIGINAL ``part_ids`` object is handed to the
        cache (not a fresh int list): the cache memoizes its per-lookup
        prep on that object's identity, which is only sound because the
        shard's lookup cache keeps the array alive and stable."""
        if len(part_ids) == 0:
            return None
        first = self.grid_partition(int(part_ids[0]))
        if first is None:
            return None
        cid = first.schema.data.value_column_id if column_id is None \
            else column_id
        ctype = first.schema.data.columns[cid].ctype
        if ctype not in (ColumnType.DOUBLE, ColumnType.HISTOGRAM):
            return None
        return self.device_cache(first.schema.schema_hash, cid,
                                 hist=(ctype == ColumnType.HISTOGRAM)), \
            part_ids

    def _grid_resolve(self, part_ids: Sequence[int],
                      column_id: Optional[int]):
        """``_grid_cache_for`` as the ``grid.resolve`` stage."""
        with TRACER.stage("grid.resolve"):
            return self._grid_cache_for(part_ids, column_id)

    def scan_grid(self, part_ids: Sequence[int], func, steps0: int,
                  nsteps: int, step_ms: int, window_ms: int,
                  column_id: Optional[int] = None, fargs: tuple = ()):
        """Serve a windowed range function directly from the device-resident
        grid (memstore/devicestore.py).  Returns ``(tags_list, vals,
        bucket_tops)`` — vals ``[S, T]`` for scalar columns, ``[S, T, hb]``
        per-bucket (with bucket_tops set) for histogram columns — or None
        when the fast path cannot serve this query; the caller then uses
        :meth:`scan_batch` + the general kernels.  This is the serving
        seam the reference places at block memory (queries read encoded
        chunks straight from BlockManager memory, never re-copying them)."""
        got = self._grid_resolve(part_ids, column_id)
        if got is None:
            return None
        cache, ids = got
        served = cache.scan_rate(ids, func, steps0, nsteps, step_ms,
                                 window_ms, fargs)
        if served is None:
            return None
        vals, tops = served
        tags_list = []
        for pid in ids:
            part = self.grid_partition(int(pid))
            if part is None:
                return None   # concurrently evicted mid-query: fall back
            tags_list.append(part.tags)
        return tags_list, vals, tops

    def scan_grid_grouped(self, part_ids: Sequence[int], func, steps0: int,
                          nsteps: int, step_ms: int, window_ms: int,
                          group_ids: Sequence[int], num_groups: int,
                          op: str, column_id: Optional[int] = None,
                          fargs: tuple = ()):
        """Fused ``agg by (g)(rate(...))`` from the device grid: the
        aggregation happens on device, so only [G, T] partials come back
        (see DeviceGridCache.scan_rate_grouped).  Returns the mergeable
        state dict or None to fall back."""
        got = self._grid_resolve(part_ids, column_id)
        if got is None:
            return None
        cache, ids = got
        return cache.scan_rate_grouped(ids, func, steps0, nsteps, step_ms,
                                       window_ms, group_ids, num_groups, op,
                                       fargs)

    def mesh_grid_plan(self, part_ids: Sequence[int], func, steps0: int,
                       nsteps: int, step_ms: int, window_ms: int,
                       group_ids, fargs: tuple = ()):
        """Device-resident staging for the SPMD mesh serving path
        (devicestore.mesh_plan; ``group_ids`` one id a series, or one
        int for them all); None -> host-batch mesh fallback."""
        got = self._grid_resolve(part_ids, None)
        if got is None:
            return None
        cache, ids = got
        return cache.mesh_plan(ids, func, steps0, nsteps, step_ms,
                               window_ms, group_ids, fargs)

    def pin_grid_device(self, device) -> None:
        """Pin this shard's grid blocks to a mesh device so the SPMD
        serving path (parallel/meshgrid.py) reads them in place — the
        multi-device analog of BlockManager-resident serving.  Re-pins
        invalidate resident blocks (they live on the old device); the
        common single-device -> mesh transition, where blocks already
        sit on the backend default device, keeps them."""
        if device is self.grid_device:
            return
        prev = self.grid_device
        self.grid_device = device
        if prev is None:
            import jax
            if device is jax.devices()[0]:
                return          # unpinned blocks already live there
        for cache in list(self.device_caches.values()):
            cache.note_repin()

    def scan_batch(self, part_ids: Sequence[int], start_time: int, end_time: int,
                   column_id: Optional[int] = None
                   ) -> tuple[list[dict], Optional[ChunkBatch]]:
        """Materialize partitions into one padded ChunkBatch + tag dicts.
        This is the TPU replacement for scanPartitions/RawDataRangeVector
        iteration (reference :1490, SelectRawPartitionsExec)."""
        self._check_integrity()
        tags_list, ts_list, val_list = [], [], []
        hist = None  # locked by the first partition: one value type per batch
        bucket_tops = None
        for pid in part_ids:
            part = self._partition_for_scan(int(pid))
            if part is None:
                continue
            cid = part.schema.data.value_column_id if column_id is None else column_id
            ctype = part.schema.data.columns[cid].ctype
            is_hist = ctype == ColumnType.HISTOGRAM
            if hist is None:
                hist = is_hist
            elif is_hist != hist:
                continue  # mixed schemas: callers scan one schema at a time
            ts, vals = part.read_range(start_time, end_time, cid)
            tags_list.append(part.tags)
            if is_hist:
                buckets, rows = vals
                if buckets is not None:
                    tops = buckets.bucket_tops()
                    if bucket_tops is None or len(tops) > len(bucket_tops):
                        bucket_tops = tops
                ts_list.append(ts)
                val_list.append(rows.astype(np.float64))
            else:
                ts_list.append(ts)
                val_list.append(vals)
        if not tags_list:
            return [], None
        if hist:
            if bucket_tops is None:
                bucket_tops = np.empty(0, dtype=np.float64)
            b = len(bucket_tops)
            val_list = [v if v.shape[1] == b
                        else np.zeros((0, b)) if v.size == 0
                        else np.pad(v, ((0, 0), (0, b - v.shape[1])), mode="edge")
                        if v.shape[1] < b else v[:, :b] for v in val_list]
            batch = build_batch(ts_list, val_list, pad_to=self.config.batch_row_pad,
                                hist=True, bucket_tops=bucket_tops,
                                pad_series_to=_round_up(len(tags_list),
                                                        self.config.batch_series_pad))
        else:
            batch = build_batch(ts_list, val_list, pad_to=self.config.batch_row_pad,
                                pad_series_to=_round_up(len(tags_list),
                                                        self.config.batch_series_pad))
        return tags_list, batch

    # ------------------------------------------------------------- metadata

    def label_values(self, label: str, filters: Sequence[ColumnFilter] = (),
                     start: int = 0, end: int = np.iinfo(np.int64).max,
                     limit: Optional[int] = None) -> list[str]:
        return self.index.label_values(label, filters, start, end, limit)

    def label_names(self, filters: Sequence[ColumnFilter] = (),
                    start: int = 0, end: int = np.iinfo(np.int64).max) -> list[str]:
        return self.index.label_names(filters, start, end)

    def part_keys(self, filters: Sequence[ColumnFilter], start: int, end: int,
                  limit: Optional[int] = None) -> list[dict[str, str]]:
        ids = self.index.part_ids_from_filters(filters, start, end, limit)
        return [self.index.tags(int(i)) for i in ids]

    @property
    def num_partitions(self) -> int:
        return len(self.partitions)

    @property
    def mem_bytes(self) -> int:
        return sum(p.mem_bytes for p in self.partitions.values())


def _round_up(n: int, to: int) -> int:
    return ((n + to - 1) // to) * to if to else n
