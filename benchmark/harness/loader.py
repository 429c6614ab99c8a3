"""Set-up phase 3: the population into the server through the record-container
edge, ``POST /ingest/<dataset>/<shard>`` (the edge upstream feeds from Kafka
and replicas dual-write on).

Containers are built with the program's own producer library
(``RecordBuilder.add_series``) and routed with its own shard-key functions, as
any producer does.  The producer and the shards' consumers take turns: a batch
of series is built and posted, then the producer waits until the shards have
ingested it, so the backlog stays one batch.  Containers are 8 MiB, not the
builder's 1 MiB: the server speaks HTTP/1.0, so every container is a new
connection, and 2.6 GB of them cross the loopback.
"""

from __future__ import annotations

import time
import urllib.request


CONTAINER_BYTES = 8 * 1024 * 1024
BATCH_SERIES = 2048       # built and posted before the shards catch up


def post(port: int, dataset: str, shard: int, container: bytes) -> None:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/ingest/{dataset}/{shard}", data=container,
        headers={"Content-Type": "application/octet-stream"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        if r.status != 200:
            raise RuntimeError(f"container edge answered HTTP {r.status}")
        r.read()


def load(pop, server, dataset: str, port: int, say) -> float:
    """Returns the seconds from the first container built to the last row
    ingested.  Raises where the shards did not ingest every row."""
    from filodb_tpu.core.record import (RecordBuilder, canonical_partkey,
                                        partition_hash, shard_key_hash)
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions

    ds_conf = next(d for d in server.config["datasets"]
                   if d["name"] == dataset)
    schema = DEFAULT_SCHEMAS[ds_conf.get("schema", "gauge")]
    options = DatasetOptions()
    mapper = server.manager.mapper(dataset)
    spread = int(ds_conf.get("spread", 1))
    shards = lambda: server.memstore.shards(dataset)      # noqa: E731
    ingested = lambda: sum(sh.stats.rows_ingested for sh in shards())  # noqa: E731
    builders = {s: RecordBuilder(schema, options,
                                 container_size=CONTAINER_BYTES)
                for s in range(mapper.num_shards)}
    want = pop.samples
    t0 = time.perf_counter()
    sent = 0
    deadline = time.time() + 600
    for a in range(0, pop.n, BATCH_SERIES):
        for s in range(a, min(a + BATCH_SERIES, pop.n)):
            tags = pop.tags(s)
            shash = shard_key_hash(tags, options)
            phash = partition_hash(tags, options)
            shard = mapper.ingestion_shard(shash, phash, spread) \
                % mapper.num_shards
            sent += builders[shard].add_series_hashed(
                pop.ts[s], [pop.vals[s]], shash, phash,
                canonical_partkey(tags))
        for shard, b in builders.items():
            for c in b.containers():
                post(port, dataset, shard, c)
        while ingested() < sent and time.time() < deadline:
            time.sleep(0.002)
    rows = ingested()
    dt = time.perf_counter() - t0
    if rows != want:
        raise RuntimeError(f"shards ingested {rows} rows of {want}")
    say(f"ingest: {pop.n} series x {pop.rows} rows = {want} samples through "
        f"POST /ingest/{dataset}/<shard> in {dt:.1f} s")
    return dt

