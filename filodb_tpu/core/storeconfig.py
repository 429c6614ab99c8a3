"""Per-dataset store/ingestion configuration.

Capability match for the reference's StoreConfig/IngestionConfig parsed from
per-dataset source config (reference: core/src/main/scala/filodb.core/store/
IngestionConfig.scala:202 and conf/timeseries-dev-source.conf:28-102).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    flush_interval_ms: int = 3_600_000        # flush-interval = 1h
    flush_task_parallelism: int = 2           # flush executor workers
    max_chunks_size: int = 400                # max rows per chunk
    groups_per_shard: int = 60
    shard_mem_size: int = 512 * 1024 * 1024   # shard-mem-size budget (bytes)
    max_buffer_pool_size: int = 10_000
    disk_ttl_seconds: int = 3 * 24 * 3600
    demand_paging_enabled: bool = True
    max_data_per_shard_query: int = 50 * 1024 * 1024
    evicted_pk_bloom_filter_capacity: int = 5_000_000
    # TPU additions: padding buckets for device batches (bounded XLA
    # recompiles — SURVEY.md §7 "Ragged data")
    batch_row_pad: int = 64
    batch_series_pad: int = 128
    # device-resident chunk store (HBM arena, reclaim-on-demand — the
    # BlockManager equivalent, reference: memory/BlockManager.scala:142)
    device_cache_bytes: int = 2 * 1024 * 1024 * 1024
    # host page cache for demand-paged partitions (decoded bytes are
    # accounted too); must cover the cold-dashboard working set or the
    # device grid cannot build from paged history (reference: ODP pages
    # into block memory whose size is config-driven,
    # DemandPagedChunkStore.scala:34 + num-block-pages)
    page_cache_bytes: int = 256 * 1024 * 1024
    grid_step_ms: Optional[int] = None   # bucket width; None = detect
    # keep grid blocks compressed in HBM (XOR-class value planes +
    # elided uniform-phase ts planes), decoded on device inside the
    # serving program; compression is taken per block only when it
    # saves >=25% (reference: compressed BinaryVectors served in place
    # from block memory, doc/compression.md)
    device_cache_compress: bool = True
    # proactive reclaim target: flush tasks trim each device cache to
    # (1-frac) of budget off the query path (reference: BlockManager
    # ensureHeadroomPercentAvailable headroom task)
    device_headroom_frac: float = 0.1
    # tag subset selecting series created as TracingTimeSeriesPartition
    # (reference: `trace-filters` config -> TimeSeriesPartition.scala:451)
    trace_filters: Optional[Mapping] = None

    @staticmethod
    def from_config(conf: Mapping) -> "StoreConfig":
        def ms(key: str, default: int) -> int:
            v = conf.get(key)
            return parse_duration_ms(v) if v is not None else default

        d = StoreConfig()
        return StoreConfig(
            flush_interval_ms=ms("flush-interval", d.flush_interval_ms),
            flush_task_parallelism=int(conf.get("flush-task-parallelism",
                                                d.flush_task_parallelism)),
            max_chunks_size=int(conf.get("max-chunks-size", d.max_chunks_size)),
            groups_per_shard=int(conf.get("groups-per-shard", d.groups_per_shard)),
            shard_mem_size=parse_size(conf.get("shard-mem-size", d.shard_mem_size)),
            max_buffer_pool_size=int(conf.get("max-buffer-pool-size",
                                              d.max_buffer_pool_size)),
            disk_ttl_seconds=ms("disk-time-to-live", d.disk_ttl_seconds * 1000) // 1000,
            demand_paging_enabled=parse_bool(conf.get("demand-paging-enabled",
                                                d.demand_paging_enabled)),
            max_data_per_shard_query=parse_size(conf.get("max-data-per-shard-query",
                                                         d.max_data_per_shard_query)),
            evicted_pk_bloom_filter_capacity=int(
                conf.get("evicted-pk-bloom-filter-capacity",
                         d.evicted_pk_bloom_filter_capacity)),
            batch_row_pad=int(conf.get("batch-row-pad", d.batch_row_pad)),
            batch_series_pad=int(conf.get("batch-series-pad", d.batch_series_pad)),
            device_cache_bytes=parse_size(conf.get("device-cache-size",
                                                   d.device_cache_bytes)),
            page_cache_bytes=parse_size(conf.get("page-cache-size",
                                                 d.page_cache_bytes)),
            grid_step_ms=(parse_duration_ms(conf["grid-step"])
                          if "grid-step" in conf else None),
            device_cache_compress=parse_plane_compress(
                conf.get("device-cache-compress",
                         d.device_cache_compress)),
            device_headroom_frac=float(
                conf.get("device-headroom-frac", d.device_headroom_frac)),
            trace_filters=conf.get("trace-filters"),
        )


@dataclasses.dataclass(frozen=True)
class IngestionConfig:
    """Binds a dataset to a source (reference: IngestionConfig — dataset,
    num-shards, min-num-nodes, sourcefactory + sourceconfig)."""

    dataset: str
    num_shards: int
    min_num_nodes: int = 1
    source_factory: Optional[str] = None
    source_config: Mapping = dataclasses.field(default_factory=dict)
    store: StoreConfig = dataclasses.field(default_factory=StoreConfig)

    def __post_init__(self):
        if self.num_shards & (self.num_shards - 1):
            raise ValueError(f"num_shards {self.num_shards} must be a power of 2")

    @staticmethod
    def from_config(conf: Mapping) -> "IngestionConfig":
        src = conf.get("sourceconfig", {})
        return IngestionConfig(
            dataset=conf["dataset"],
            num_shards=int(conf["num-shards"]),
            min_num_nodes=int(conf.get("min-num-nodes", 1)),
            source_factory=conf.get("sourcefactory"),
            source_config=src,
            store=StoreConfig.from_config(src.get("store", {})),
        )


_UNITS_MS = {"ms": 1, "s": 1000, "m": 60_000, "h": 3_600_000, "d": 86_400_000,
             "minute": 60_000, "minutes": 60_000, "hour": 3_600_000,
             "hours": 3_600_000, "day": 86_400_000, "days": 86_400_000,
             "second": 1000, "seconds": 1000}


def parse_bool(v) -> bool:
    """Config booleans arrive as real bools or as strings from config
    files; bool('false') == True would silently defeat every string-
    valued kill switch."""
    if isinstance(v, str):
        lv = v.strip().lower()
        if lv in ("true", "yes", "on", "1"):
            return True
        if lv in ("false", "no", "off", "0"):
            return False
        raise ValueError(f"not a boolean config value: {v!r}")
    return bool(v)


def parse_plane_compress(v) -> bool:
    """``device-cache-compress``: a boolean, or ``dataset``: compressed, by
    a configuration that counts on the dataset's local shards agreeing on
    one set of plane shapes (memstore/gridshapes.py), so that what the node
    holds in HBM does not depend on which shard was asked first.  No switch:
    the caches agree whatever is written here.  The word is for a program
    from before they could: its boolean parser refuses it, and that node
    does not start on a configuration it cannot keep."""
    if isinstance(v, str) and v.strip().lower() == "dataset":
        return True
    return parse_bool(v)


def parse_duration_ms(v) -> int:
    """'1 hour' / '5m' / '300ms' / int millis -> millis (HOCON-style)."""
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip()
    for unit in sorted(_UNITS_MS, key=len, reverse=True):
        if s.endswith(unit):
            return int(float(s[: -len(unit)].strip()) * _UNITS_MS[unit])
    return int(float(s))


_SIZE_UNITS = {"kb": 1 << 10, "mb": 1 << 20, "gb": 1 << 30, "k": 1 << 10,
               "m": 1 << 20, "g": 1 << 30, "b": 1}


def parse_size(v) -> int:
    """'512MB' / '2GB' / int bytes -> bytes."""
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip().lower()
    for unit in sorted(_SIZE_UNITS, key=len, reverse=True):
        if s.endswith(unit):
            return int(float(s[: -len(unit)].strip()) * _SIZE_UNITS[unit])
    return int(float(s))
