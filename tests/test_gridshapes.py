"""``memstore/gridshapes.py``: what the device caches of one dataset's
local shards agree on, and ``xorgrid.pack_vals(agree=...)``, the pack that
keeps to it."""

import threading
import types

import numpy as np
import pytest

from filodb_tpu.codecs import xorgrid
from filodb_tpu.memstore import gridshapes
from filodb_tpu.memstore.gridshapes import GridShapes, pad_lanes


def shards(*counts):
    return lambda: [types.SimpleNamespace(num_partitions=n) for n in counts]


@pytest.mark.parametrize("counts,need,want", [
    ((102400,), 102400, 102400),                 # one shard: its own
    ((25021, 24771, 26363, 26245), 25088, 26368),   # dev-4shard: all one
    ((25021, 24771, 26363, 26245), 24832, 26368),
    ((25021, 24771, 26363, 26245), 26368, 26368),
    ((1000000, 900), 1024, 1024),    # a sibling far wider: no snap
    ((1000000, 900), 128, 128),      # few lanes staged of a large shard
    ((), 256, 256),
])
def test_lanes_snap_to_the_widest_sibling_that_is_close(counts, need, want):
    assert GridShapes(shards(*counts)).lanes_for(need) == want
    assert pad_lanes(0) == 128 and pad_lanes(129) == 256


def test_builders_of_one_block_wait_for_each_other():
    shapes = GridShapes(shards())
    needs = [{"p16": 23424, "raw": 1408}, {"p16": 23808, "raw": 1280},
             {"p16": 24960, "raw": 1408}, {"p8": 128, "p16": 24832}]
    got: dict = {}
    inside = threading.Barrier(len(needs))

    def build(i):
        with shapes.building(("blk", 1)) as agree:
            inside.wait(timeout=30)       # all are staging before any packs
            got[i] = agree(needs[i])

    ts = [threading.Thread(target=build, args=(i,)) for i in range(len(needs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in ts)
    want = {"p8": 128, "p16": 24960, "raw": 1408}
    assert all(got[i] == want for i in range(len(needs))), got
    # a later builder takes what was agreed where it fits, widens it
    # where it needs more; another block starts anew
    with shapes.building(("blk", 1)) as agree:
        assert agree({"p16": 24000}) == want
        assert agree({"raw": 1536}) == dict(want, raw=1536)
    with shapes.building(("blk", 2)) as agree:
        assert agree({"p16": 128}) == {"p16": 128}


def test_a_builder_that_leaves_without_packing_holds_nobody(monkeypatch):
    monkeypatch.setattr(gridshapes, "AGREE_WAIT_S", 30.0)
    shapes = GridShapes(shards())
    entered, out = threading.Event(), {}

    def fails():
        try:
            with shapes.building("k"):
                entered.set()
                raise RuntimeError("staging failed")
        except RuntimeError:
            pass

    def packs():
        with shapes.building("k") as agree:
            out["w"] = agree({"p16": 256})

    with shapes.building("k") as agree:      # a third, still staging ...
        t = threading.Thread(target=packs)
        t.start()
        f = threading.Thread(target=fails)
        f.start()
        f.join(timeout=10)
        assert entered.is_set() and not f.is_alive()
        t.join(timeout=0.3)
        assert t.is_alive()                  # ... is waited for
        assert agree({"p16": 384}) == {"p16": 384}
    t.join(timeout=10)
    assert not t.is_alive() and out["w"] == {"p16": 384}


def test_the_wait_for_a_stalled_sibling_ends(monkeypatch):
    monkeypatch.setattr(gridshapes, "AGREE_WAIT_S", 0.05)
    shapes = GridShapes(shards())
    with shapes.building("k"):
        with shapes.building("k") as agree:
            assert agree({"raw": 128}) == {"raw": 128}


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pack_keeps_to_what_was_agreed_and_decodes_bit_exact(dtype):
    rng = np.random.default_rng(3)
    vals = (1e6 + np.cumsum(rng.integers(0, 50, (128, 3000)), axis=0)) \
        .astype(dtype)
    vals[:, :40] = rng.random((128, 40)).astype(dtype) * 1e9   # raw lanes
    alone = xorgrid.pack_vals(vals)
    asked = {}

    def agree(need):
        asked.update(need)
        return {k: n + 128 for k, n in need.items()} | {"p8": 256}

    packed = xorgrid.pack_vals(vals, agree=agree)
    assert asked == {k: v.shape[1] for k, v in alone.planes.items()
                     if k in ("p8", "p16", "p32", "raw") and v.shape[1]}
    for k, n in asked.items():
        assert packed.planes[k].shape[1] == n + 128
    assert packed.planes["p8"].shape[1] == 256       # pad alone
    for p in (packed, alone):
        assert np.ascontiguousarray(xorgrid.unpack_vals(p)).tobytes() \
            == vals.tobytes()
    # pad lanes are nobody's: every original lane maps to a real one
    assert len(set(packed.inv.tolist())) == vals.shape[1]
