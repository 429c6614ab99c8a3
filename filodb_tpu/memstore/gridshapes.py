"""What the device grid caches of one dataset's local shards agree on, so
that their serving programs have the SAME abstract shapes.

A serving program (``devicestore._fused_progs``) is compiled for the
shapes of the planes it is handed: the block's lane width and, for a
compressed block, the width of every XOR class plane.  Left to itself each
shard pads its own lane count and packs its own class mix, so four shards
of one node compile every program four times and keep four executables
loaded, each a few MB of HBM (PERF.md, PR 29: as much HBM as the samples),
and a stack size first met on one shard compiles there, under traffic.
Shards of one dataset that hash their series evenly are within a few per
cent of each other, so a little padding makes them one shape:

- **lanes**: a cache whose own padded need is within ``1/LANE_SLACK`` of
  the widest sibling's (that shard's partition count, padded) takes the
  sibling's width.  Stateless: every shard computes the same answer from
  what the shards hold, whichever plans first.
- **class planes**: caches building the same block at the same time (a
  query that fans out over the shards builds them together) take, class by
  class, the widest of what each needs.  A cache that builds the block
  later takes what was agreed where it fits; where it needs more it widens
  the agreement for those still to come, and its own programs are its own
  until its siblings rebuild.  Agreement costs shape-sharing only, never an
  answer: a pad lane decodes to a constant the consumers drop.

A dataset with one local shard agrees with itself: nothing changes."""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Iterable

LANE_PAD = 128            # the Mosaic lane tile: every width's granularity
LANE_SLACK = 8            # snap to a sibling at most 1/8 wider than the need
AGREE_WAIT_S = 60.0       # a sibling's staging is tens of seconds at most
ROUNDS_KEPT = 64          # (block, width) agreements remembered


def pad_lanes(n: int) -> int:
    return max(LANE_PAD, -(-n // LANE_PAD) * LANE_PAD)


class _Round:
    __slots__ = ("inside", "proposed", "widths")

    def __init__(self):
        self.inside = 0        # builders of this block right now
        self.proposed = 0      # ... of which have said what they need
        self.widths: dict = {}


class GridShapes:
    def __init__(self, shards: Callable[[], Iterable]):
        self._shards = shards
        self._cond = threading.Condition()
        self._rounds: dict = {}

    def lanes_for(self, need: int) -> int:
        """The block width for a cache that needs ``need`` lanes (padded
        already): the widest sibling's where that is close, else its own."""
        widest = max((pad_lanes(s.num_partitions) for s in self._shards()),
                     default=need)
        return widest if need <= widest <= need + need // LANE_SLACK \
            else need

    @contextlib.contextmanager
    def building(self, key):
        """Around one block build, from before its staging: yields
        ``agree(need) -> widths`` for the pack (``need`` and ``widths``:
        ``{class plane: lanes}``), which waits for the siblings that are
        inside ``building(key)`` too and have yet to say what they need."""
        with self._cond:
            r = self._rounds.pop(key, None) or _Round()
            self._rounds[key] = r             # newest last
            while len(self._rounds) > ROUNDS_KEPT:
                old = next(iter(self._rounds))
                if self._rounds[old].inside:
                    break
                del self._rounds[old]
            r.inside += 1
        said = False

        def agree(need: dict) -> dict:
            nonlocal said
            with self._cond:
                for k, n in need.items():
                    r.widths[k] = max(r.widths.get(k, 0), n)
                if not said:
                    said = True
                    r.proposed += 1
                self._cond.notify_all()
                deadline = time.monotonic() + AGREE_WAIT_S
                while r.proposed < r.inside:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._cond.wait(left)
                return dict(r.widths)

        try:
            yield agree
        finally:
            with self._cond:
                r.inside -= 1
                if said:
                    r.proposed -= 1
                self._cond.notify_all()
