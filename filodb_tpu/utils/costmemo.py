"""A bounded memo that prices its entries by what they cost to make again.

The served path memoises what it derives from a lookup result (the
lookup itself, its lane resolution, the grid plan) in a few entries a
shard.  One of those entries covers all of a shard's ~25 000 lanes (a
workspace-wide panel, milliseconds of host work under the grid lock to
derive again) and the next 64 (a namespace panel, microseconds): a memo
that empties itself at a count, or evicts by recency alone, lets 800
namespaces push the dear entry out every few requests (PERF.md section
6, PR 34 and PR 35).

:class:`CostMemo` evicts ONE entry when full, never all of them: the
entry with the least ``clock at last use + cost`` (GreedyDual), the
clock moving up to the evicted entry's mark.  Entries of equal cost
leave in order of last use (plain LRU); an entry that cost 400 times its
neighbours outlives, unused, 400 turnovers of the whole memo by them,
and no longer.
"""

from __future__ import annotations

import threading
from itertools import islice


class CostMemo:
    """``get`` / ``put`` / ``clear`` over at most ``cap`` entries, each
    taking its own lock (lookups come from every worker thread)."""

    def __init__(self, cap: int) -> None:
        self.cap = cap
        # key -> [value, mark, cost], in order of last use.  A plain dict
        # (an entry used again is taken out and put back at the end): an
        # OrderedDict's iterators look every key up again, and a lookup's
        # key hashes its filters in Python
        self._entries: dict = {}
        self._clock = 0.0
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                return None
            self._entries[key] = entry
            entry[1] = self._clock + entry[2]
            return entry[0]

    def put(self, key, value, cost: float) -> None:
        """Keep ``value``; ``cost`` is what deriving it again would take,
        in any unit the memo's entries share (lanes requested)."""
        with self._lock:
            entries = self._entries
            entries.pop(key, None)
            entries[key] = [value, self._clock + cost, cost]
            while len(entries) > self.cap:
                # the oldest of the cheapest, never the one just added
                # (the last in order of use)
                victim, entry = min(islice(entries.items(), len(entries) - 1),
                                    key=_mark)
                self._clock = entry[1]
                del entries[victim]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def discard(self, unwanted) -> None:
        """Let go of every entry whose value ``unwanted(value)`` is true
        of (a plan made of device planes that were just replaced)."""
        with self._lock:
            for key in [k for k, entry in self._entries.items()
                        if unwanted(entry[0])]:
                del self._entries[key]

    def values(self) -> list:
        """The kept values, least recently used first."""
        with self._lock:
            return [entry[0] for entry in self._entries.values()]

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries


def _mark(item: tuple) -> float:
    return item[1][1]
