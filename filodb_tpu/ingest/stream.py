"""Ingestion stream abstraction: per-shard streams of record containers.

Capability match for the reference's IngestionStream/Factory (reference:
coordinator/src/main/scala/filodb.coordinator/IngestionStream.scala:14,43
— one stream per shard, messages are RecordContainer bytes; Kafka binds a
shard to one topic partition, KafkaIngestionStream.scala:24-63).  The
factory is resolved by name from the ingestion config's ``sourcefactory``
(reflection in the reference; a registry here).
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

# A stream element is (offset, container_bytes) — offsets are the
# checkpointable positions (Kafka offsets in the reference).
StreamElement = tuple[int, bytes]


class IngestionStream:
    """One shard's container stream."""

    def get(self) -> Iterator[StreamElement]:
        raise NotImplementedError

    def teardown(self) -> None:
        pass


class IngestionStreamFactory:
    def create(self, dataset: str, shard: int,
               offset: Optional[int] = None) -> IngestionStream:
        """``offset``: resume position — elements below it may be skipped
        by the source (recovery replays handle the rest via watermarks)."""
        raise NotImplementedError


class ListStream(IngestionStream):
    """Deterministic in-memory stream (tests / CSV-style sources)."""

    def __init__(self, elements: Iterable[StreamElement],
                 start_offset: Optional[int] = None):
        self._elements = list(elements)
        self._start = start_offset

    def get(self) -> Iterator[StreamElement]:
        for off, c in self._elements:
            if self._start is None or off >= self._start:
                yield off, c


class ListStreamFactory(IngestionStreamFactory):
    """shard -> predefined element list (reference: CsvStream used by
    multi-jvm recovery specs for deterministic streams)."""

    def __init__(self, by_shard: dict[int, list[StreamElement]]):
        self.by_shard = by_shard

    def create(self, dataset, shard, offset=None) -> IngestionStream:
        return ListStream(self.by_shard.get(shard, []), offset)


class QueueStream(IngestionStream):
    """Live push stream: producers enqueue, the ingestion loop drains.
    The in-process stand-in for one Kafka topic partition.  ``close()``
    wakes the current consumer (one sentinel ends one ``get`` iterator);
    pushes keep working across consumer generations, like a Kafka
    partition outliving any one consumer."""

    _SENTINEL = (None, None)

    def __init__(self, maxsize: int = 0, start_offset: int = 0):
        # unbounded by default: push must never block while holding the
        # offset lock (a bounded queue + stopped consumer would deadlock
        # ensure_offset/other producers against a blocked put)
        self._q: queue.Queue = queue.Queue(maxsize)
        self._next_offset = start_offset
        self._lock = threading.Lock()
        self._close_pending = False
        # when the element ``get`` last yielded was pushed
        # (``time.time()``): the edge's 200 follows the push, so this is
        # where a container's visibility lag starts.  ``_arrivals`` holds
        # (offset, time) of what is still queued, oldest first
        self.last_arrived: Optional[float] = None
        self._arrivals: collections.deque = collections.deque()

    def push(self, container: bytes) -> int:
        # assign AND enqueue under the lock: out-of-order offsets would turn
        # into silent data loss at the checkpoint/watermark layer
        with self._lock:
            off = self._next_offset
            self._next_offset += 1
            self._arrivals.append((off, time.time()))
            self._q.put((off, container))
        return off

    def ensure_offset(self, offset: int) -> None:
        """Fast-forward numbering so post-restart pushes land above the
        recovery checkpoints (a real Kafka partition's offsets are durable;
        an in-process queue's must be bumped explicitly)."""
        with self._lock:
            self._next_offset = max(self._next_offset, offset)

    def end_offset(self) -> int:
        """The next offset to be assigned — the broker ``end_offset``
        analog the watermark ledger reads for lag (ISSUE 6)."""
        with self._lock:
            return self._next_offset

    def close(self) -> None:
        """Wake the current consumer.  Idempotent until delivered: closing
        twice before a consumer sees the sentinel enqueues it once, so a
        restarted consumer never dies on a stale sentinel."""
        with self._lock:
            if self._close_pending:
                return
            self._close_pending = True
            self._q.put(self._SENTINEL)

    def get(self) -> Iterator[StreamElement]:
        while True:
            item = self._q.get()
            if item == self._SENTINEL:
                with self._lock:
                    self._close_pending = False
                return
            arrivals, self.last_arrived = self._arrivals, None
            while arrivals and arrivals[0][0] <= item[0]:
                at, when = arrivals.popleft()
                if at == item[0]:
                    self.last_arrived = when
            yield item

    def teardown(self) -> None:
        self.close()


class QueueStreamFactory(IngestionStreamFactory):
    """Lazily creates one QueueStream per (dataset, shard); producers fetch
    the same stream by key to push into it."""

    def __init__(self) -> None:
        self._streams: dict[tuple[str, int], QueueStream] = {}
        self._lock = threading.Lock()

    def stream_for(self, dataset: str, shard: int) -> QueueStream:
        with self._lock:
            key = (dataset, shard)
            st = self._streams.get(key)
            if st is None:
                st = self._streams[key] = QueueStream()
            return st

    def create(self, dataset, shard, offset=None) -> IngestionStream:
        st = self.stream_for(dataset, shard)
        if offset is not None:
            st.ensure_offset(offset)
        return st


_FACTORIES: dict[str, Callable[..., IngestionStreamFactory]] = {}


def register_source_factory(name: str,
                            ctor: Callable[..., IngestionStreamFactory]) -> None:
    """Registry keyed like the reference's ``sourcefactory`` class names."""
    _FACTORIES[name] = ctor


def source_factory(name: str, **kwargs) -> IngestionStreamFactory:
    if name not in _FACTORIES:
        raise ValueError(f"unknown sourcefactory {name!r}; "
                         f"known: {sorted(_FACTORIES)}")
    return _FACTORIES[name](**kwargs)


register_source_factory("queue", QueueStreamFactory)
