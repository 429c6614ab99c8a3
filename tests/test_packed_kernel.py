"""Compressed-resident equivalence: the fused on-device XOR-class
decode (ops/grid.py rate_grid_packed, and the device store's
grouped_packed program over it) must be
bit-identical to the CPU codec decode (codecs/xorgrid.py unpack_vals)
and agree with the decoded-plane kernels across the layout's edge cases
— NaN payloads, constant runs, sign flips, partial final tiles, mixed
classes, promote/pad alignment.  Pallas runs in interpret mode so the
whole sweep executes in CPU CI (ISSUE 3 satellite).

ISSUE 14 widens the sweep to the histogram bucket-plane substrate
(stride packs + hist_grid_grouped_packed),
the generic columnar scan-filter-topK program, and the devicestore
mid-stream bucket-widening path (16 -> 20 buckets)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.codecs.xorgrid import (LANE_BLOCK, UNPADDED_MAX, pack_vals,
                                       unpack_vals)
from filodb_tpu.core.histogram import quantile_bulk
from filodb_tpu.memstore import devicestore
from filodb_tpu.ops import histogram_ops
from filodb_tpu.ops.grid import (GridQuery, event_topk_grid_packed,
                                 hist_grid_grouped_packed, packed_width,
                                 rate_grid_packed, rate_grid_ref)
from tests import oracle

STEP = 60_000


def _counters(rng, B, L, dtype=np.float32):
    """Integer-valued counters with a pinned f32 exponent: residuals
    provably fit 16 bits."""
    start = (2 ** 23 + 128 * rng.integers(0, 2 ** 15, L)).astype(dtype)
    inc = 128 * rng.integers(1, 8, (B, L))
    return (start[None, :] + np.cumsum(inc, axis=0)).astype(dtype)


def _edge_plane(rng, B, L):
    """A plane stressing every classification edge case at once."""
    v = np.empty((B, L), np.float32)
    n = L // 8
    v[:, :n] = 5.0                                      # constant run
    v[:, n:2 * n] = np.where(np.arange(B)[:, None] % 2 == 0,
                             1.5, -1.5)                 # sign flips
    # NaN payload bits must survive decode bit-for-bit
    pay = np.frombuffer(np.uint32(0x7fc01dea).tobytes(),
                        dtype=np.float32)[0]
    v[:, 2 * n:3 * n] = pay
    v[:, 3 * n:4 * n] = np.nan                          # all-NaN lanes
    v[:, 4 * n:5 * n] = _counters(rng, B, n)            # narrow class
    v[:, 5 * n:6 * n] = rng.random((B, n)) * 100        # incompressible
    v[:, 6 * n:7 * n] = _counters(rng, B, n)
    # partial fill: leading + trailing NaN around a counter run
    v[:, 7 * n:] = _counters(rng, B, L - 7 * n)
    v[:B // 4, 7 * n:] = np.nan
    v[-B // 4:, 7 * n:] = np.nan
    return v


class TestPackRoundtrip:
    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize("L", [256, 137, 129, 1024])
    def test_edge_cases_bit_identical(self, seed, L):
        """Seeded sweep: whatever mix of classes/pads/promotions the
        aligner picks, the CPU decode reproduces the input bits."""
        rng = np.random.default_rng(seed)
        v = _edge_plane(rng, 64, L)
        pk = pack_vals(v)
        if pk is None:
            pytest.skip("mix did not pay at this width")
        out = unpack_vals(pk)
        np.testing.assert_array_equal(out.view(np.uint32),
                                      v.view(np.uint32))

    def test_f64_roundtrip_bit_identical(self):
        rng = np.random.default_rng(9)
        v = (1_000_000 + np.cumsum(rng.integers(-500, 500, (128, 192)),
                                   axis=0)).astype(np.float64)
        v[:, :40] = np.nan
        pk = pack_vals(v)
        assert pk is not None
        np.testing.assert_array_equal(unpack_vals(pk).view(np.uint64),
                                      v.view(np.uint64))

    def test_partial_final_tile_stays_unpadded(self):
        """A narrow class plane (< LANE_BLOCK) may skip alignment; the
        decode must still be exact and the footprint must not balloon."""
        rng = np.random.default_rng(2)
        v = np.full((128, 128), np.nan, np.float32)
        v[:, :6] = (rng.random((128, 6)).astype(np.float32) + 1) * 100
        pk = pack_vals(v)
        assert pk is not None
        assert pk.planes["raw"].shape[1] == 6          # unpadded tail
        np.testing.assert_array_equal(unpack_vals(pk).view(np.uint32),
                                      v.view(np.uint32))

    def test_alignment_invariant(self):
        """Every class plane is lane-block aligned OR narrow enough for
        a whole-plane kernel block (the encode-side guarantee the fused
        kernels rely on)."""
        for seed in range(6):
            rng = np.random.default_rng(seed)
            v = _edge_plane(rng, 64, 512)
            pk = pack_vals(v)
            if pk is None:
                continue
            for key in ("p8", "p16", "raw"):
                p = pk.planes.get(key)
                if p is None:
                    continue
                n = p.shape[1]
                assert n % LANE_BLOCK == 0 or n <= UNPADDED_MAX, (key, n)

    def test_min_width_forces_single_identity_plane(self):
        """The hist kernel's group-contiguity contract: class-16-guaranteed
        counters with min_width=16 pack as ONE p16 plane in identity
        lane order."""
        rng = np.random.default_rng(3)
        L = 512
        v = _counters(rng, 59, L)
        v[:, 100:140] = np.nan                    # padding lanes
        pk = pack_vals(v, min_width=16)
        assert pk.planes["p16"].shape[1] == L
        assert pk.planes["raw"].shape[1] == 0
        assert (pk.inv == np.arange(L)).all()
        np.testing.assert_array_equal(unpack_vals(pk).view(np.uint32),
                                      v.view(np.uint32))


def _pack_dev(v, phase=None, **kw):
    pk = pack_vals(v, phase=phase, **kw)
    assert pk is not None
    np.testing.assert_array_equal(unpack_vals(pk).view(np.uint32),
                                  v.view(np.uint32))
    return pk, {k: jnp.asarray(a) for k, a in pk.planes.items()}


def _served_grouped_packed(dev, inv, garr, num_groups, q):
    """``sum by (g)`` the way ``_dispatch_grouped`` serves a packed
    plan: the group map scattered through the pack's ``inv`` (pad lanes
    keep the drop bucket) into the ``devicestore.grouped_packed``
    program.  -> (sum, count) [G, T]."""
    garr_pk = np.full(packed_width(dev), num_groups, np.int32)
    garr_pk[inv] = garr
    both = devicestore._fused_progs()["grouped_packed"](
        dev, 0, jnp.asarray(garr_pk), q=q, row0=0, use_phase=True,
        num_groups=num_groups, op="sum", interpret=True)
    return np.asarray(both)


# A "last 15 minutes" panel over a whole 128-row block: 60 steps and a
# [5m] window at a 15 s scrape (K = 20), 79 rows a span, so its first row
# can stand at any of the block's rows 0..49
BLOCK_T, BLOCK_K = 60, 20
BLOCK_OFFSETS = range(devicestore.BLOCK_BUCKETS
                      - (BLOCK_T + BLOCK_K - 1) + 1)


def _oracle_block(op, v, phase, K, g=STEP):
    """``tests/oracle.py`` over every window of a whole block, a lane at
    a time, in f64: window t covers rows [t, t+K-1] and ends at t*g,
    so row c's sample is stamped (c - K)*g + phase.  -> [windows, L]."""
    B, L = v.shape
    ts = (np.arange(B)[:, None] - K) * g + phase[None, :].astype(np.int64)
    ends = (B - K) * g
    name = {"rate": "rate", "sum": "sum_over_time", "last": "last"}[op]
    return np.stack([oracle.range_fn(name, ts[:, s], v[:, s], 0, ends, g,
                                     K * g) for s in range(L)], axis=1)


@pytest.fixture(scope="module")
def phase_block():
    """A packed 128-row block of phase-uniform counters (an empty band
    of lanes in it), its device planes and the oracle's every window."""
    rng = np.random.default_rng(11)
    B, L = devicestore.BLOCK_BUCKETS, 512
    v = _counters(rng, B, L)
    v[:, 200:230] = np.nan
    phase = rng.integers(1, STEP, L).astype(np.int32)
    pk, dev = _pack_dev(v, phase=phase)
    return v, phase, pk, dev, _oracle_block("rate", v, phase, BLOCK_K)


@pytest.fixture(scope="module")
def edge_block():
    """A packed 128-row block of every class edge case (NaN payloads,
    holes, mixed classes), its device planes and the oracle's every
    window of the two TS-free ops the sweep serves."""
    rng = np.random.default_rng(12)
    B, L = devicestore.BLOCK_BUCKETS, 512
    v = _edge_plane(rng, B, L)
    phase = np.full(L, STEP, np.int32)       # bucket-edge stamps
    pk, dev = _pack_dev(v)
    want = {op: _oracle_block(op, v, phase, BLOCK_K)
            for op in ("last", "sum")}
    return v, pk, dev, want


class TestFusedKernelEquivalence:
    """rate_grid_packed, alone and under the device store's grouped
    reduce, in interpret mode vs the decoded-plane reference and the
    oracle."""

    @pytest.mark.parametrize("row0", BLOCK_OFFSETS)
    def test_phase_rate_matches_ref(self, row0, phase_block):
        """Every offset of a span in a block: the kernel rotates its
        traced row0 to the top; XLA decode path and oracle agree."""
        v, phase, pk, dev, want = phase_block
        T, K = BLOCK_T, BLOCK_K
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, is_rate=True,
                      dense=True)
        out = np.asarray(rate_grid_packed(dev, 0, q, row0=row0,
                                          interpret=True,
                                          use_phase=True))[:, pk.inv]
        ref = np.asarray(rate_grid_ref(
            None, jnp.asarray(v[row0:row0 + T + K - 1]), 0, q,
            phase=phase))
        fin = np.isfinite(ref)
        assert (np.isfinite(out) == fin).all()
        np.testing.assert_allclose(out[fin], ref[fin], rtol=2e-5)
        want = want[row0:row0 + T]
        assert (np.isfinite(want) == fin).all()
        np.testing.assert_allclose(out[fin], want[fin], rtol=2e-6)

    @pytest.mark.parametrize("row0", BLOCK_OFFSETS)
    @pytest.mark.parametrize("op", ["last", "sum"])
    def test_free_op_every_offset(self, op, row0, edge_block):
        """TS-free ``last`` and ``sum`` over a mixed-class block at every
        offset: ``last`` exact, ``sum`` within the cell's 2e-6, against
        the XLA decode path and the oracle."""
        v, pk, dev, want = edge_block
        T, K = BLOCK_T, BLOCK_K
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, op=op,
                      is_rate=False, dense=False)
        out = np.asarray(rate_grid_packed(dev, 0, q, row0=row0,
                                          interpret=True))[:, pk.inv]
        ref = np.asarray(rate_grid_ref(
            None, jnp.asarray(v[row0:row0 + T + K - 1]), 0, q))
        want = want[op][row0:row0 + T]
        fin = np.isfinite(want)
        assert (np.isfinite(out) == fin).all()
        assert (np.isfinite(ref) == fin).all()
        if op == "last":
            np.testing.assert_array_equal(out[fin], want[fin])
            np.testing.assert_array_equal(out[fin], ref[fin])
        else:
            np.testing.assert_allclose(out[fin], want[fin], rtol=2e-6)
            np.testing.assert_allclose(out[fin], ref[fin], rtol=2e-6)

    @pytest.mark.parametrize("op", ["sum", "max", "count", "last"])
    def test_free_ops_match_ref(self, op):
        """TS_FREE ops over a MIXED-class pack (p8 + p16 + raw planes),
        including the non-dense general path with NaN holes."""
        rng = np.random.default_rng(12)
        B, L = 64, 512
        v = _edge_plane(rng, B, L)
        pk, dev = _pack_dev(v)
        T, K = 12, 4
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, op=op,
                      is_rate=False, dense=False)
        out = np.asarray(rate_grid_packed(dev, 0, q, row0=2,
                                          interpret=True))[:, pk.inv]
        ref = np.asarray(rate_grid_ref(None,
                                       jnp.asarray(v[2:2 + T + K - 1]),
                                       0, q))
        fin = np.isfinite(ref)
        assert (np.isfinite(out) == fin).all()
        np.testing.assert_allclose(out[fin], ref[fin], rtol=1e-6)

    def test_grouped_packed_matches_decoded(self):
        """The served grouped_packed program (decode in the kernel, the
        XLA reduce after it) over a MIXED-class pack, where packed lane
        order is not the request's, vs the decoded-plane reference + a
        per-lane NumPy reduce."""
        rng = np.random.default_rng(13)
        B, L, G = 59, 1024, 8
        v = _counters(rng, B, L)
        v[:, 256:384] = (rng.random((B, 128)) * 100).astype(np.float32)
        v[:, 500:520] = np.nan
        phase = rng.integers(1, STEP, L).astype(np.int32)
        pk, dev = _pack_dev(v, phase=phase)
        assert not (pk.inv == np.arange(L)).all()
        T, K = 20, 5
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, is_rate=True,
                      dense=True)
        garr = rng.integers(0, G + 1, L).astype(np.int32)   # G = dropped
        got = _served_grouped_packed(dev, pk.inv, garr, G, q)
        ref = np.asarray(rate_grid_ref(None, jnp.asarray(v[:T + K - 1]),
                                       0, q, phase=phase))
        want = oracle.grouped_reduce(ref, garr, G, "sum")
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_allclose(got[0], want[0], rtol=2e-5)

    def test_packed_width_and_validation(self):
        rng = np.random.default_rng(14)
        v = _counters(rng, 64, 256)
        pk, dev = _pack_dev(v, min_width=16)
        assert packed_width(dev) == 256
        # a span longer than the block; where one that fits starts is
        # the dispatcher's proof (the offset is traced)
        q = GridQuery(nsteps=62, kbuckets=4, gstep_ms=STEP, dense=True)
        with pytest.raises(ValueError, match="rows"):
            rate_grid_packed(dev, 0, q, row0=0, interpret=True,
                             use_phase=True)
        qbad = GridQuery(nsteps=8, kbuckets=4, gstep_ms=STEP, op="rate",
                         dense=True)
        with pytest.raises(ValueError, match="ts plane"):
            rate_grid_packed(dev, 0, qbad, interpret=True,
                             use_phase=False)

    def test_grouped_packed_drops_pad_lanes(self):
        """Alignment-pad lanes decode to finite 0.0 series: the served
        grouped program must drop them through the group map, or each
        would count as a live series of some group."""
        rng = np.random.default_rng(16)
        B, L = 64, 896
        v = _counters(rng, B, L)
        pk = pack_vals(v, min_width=16)
        # append 128 zero pad lanes to the class plane exactly as the
        # aligner would (zero residuals, zero meta -> constant 0.0)
        planes = dict(pk.planes)
        planes["p16"] = np.pad(planes["p16"], ((0, 0), (0, 128)))
        planes["m16"] = np.pad(planes["m16"], ((0, 0), (0, 128)))
        planes["z16"] = np.pad(planes["z16"], (0, 128))
        planes["first"] = np.pad(planes["first"], (0, 128))
        dev = {k: jnp.asarray(a) for k, a in planes.items()}
        assert packed_width(dev) == L + 128 > dev["inv"].shape[0]
        T, K, G = 8, 4, 7
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, dense=True)
        garr = (np.arange(L) // 128).astype(np.int32)
        got = _served_grouped_packed(dev, pk.inv, garr, G, q)
        ref = np.asarray(rate_grid_ref(
            None, jnp.asarray(v[:T + K - 1]), 0, q,
            phase=np.zeros(L, np.int32)))
        want = oracle.grouped_reduce(ref, garr, G, "sum")
        assert (want[1] == 128).all()
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_allclose(got[0], want[0], rtol=2e-5)

    def test_event_topk_matches_ref(self):
        """Generic columnar scan-filter-topK over a MIXED-class pack:
        the packed-order contract composes garr through inv, filter
        column packed with a DIFFERENT layout composed via filt_pos."""
        rng = np.random.default_rng(21)
        B, L, G, k = 64, 512, 8, 3
        v = _edge_plane(rng, B, L)
        pk, dev = _pack_dev(v)
        fv = _counters(rng, B, L)
        pkf, devf = _pack_dev(fv, min_width=16)
        assert (pkf.inv == np.arange(L)).all()
        T, K = 12, 4
        qs = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, op="sum",
                       is_rate=False, dense=False)
        ql = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, op="last",
                      is_rate=False, dense=True)
        garr_orig = (np.arange(L) % G).astype(np.int32)
        # garr and filt_pos are in the VALUE pack's lane order
        npk = packed_width(dev)
        garr_pk = np.full(npk, G, np.int32)
        garr_pk[pk.inv] = garr_orig
        filt_pos = np.zeros(npk, np.int64)
        filt_pos[pk.inv] = pkf.inv          # value-pos -> filter-pos
        vals, idx = event_topk_grid_packed(
            dev, 0, qs, k, jnp.asarray(garr_pk), G,
            filt_packed=devf, filt_op="gt",
            filt_thresh=float(np.median(fv[B // 2])), filt_q=ql,
            filt_pos=jnp.asarray(filt_pos), interpret=True)
        # oracle: decoded-plane reference + numpy reduce + ranking
        sv = np.asarray(rate_grid_ref(None, jnp.asarray(v[:T + K - 1]),
                                      0, qs))
        sf = np.asarray(rate_grid_ref(None, jnp.asarray(fv[:T + K - 1]),
                                      0, ql))
        masked = np.where(sf > float(np.median(fv[B // 2])), sv, np.nan)
        fin = np.isfinite(masked)
        gs = np.zeros((G, T))
        gc = np.zeros((G, T))
        for c in range(L):
            g = garr_orig[c]
            gs[g] += np.where(fin[:, c], masked[:, c], 0.0)
            gc[g] += fin[:, c]
        ranked = np.where(gc > 0, gs, -np.inf)
        got_v, got_i = np.asarray(vals), np.asarray(idx)
        for t in range(T):
            order = np.argsort(-ranked[:, t], kind="stable")[:k]
            want = np.where(np.isfinite(ranked[order, t]),
                            ranked[order, t], np.nan)
            np.testing.assert_allclose(got_v[t], want, rtol=1e-5,
                                       equal_nan=True)
            live = np.isfinite(want)
            assert set(got_i[t][live]) == set(order[live])
            assert (got_i[t][~live] == -1).all()

    def test_event_topk_bottomk_and_bad_filter_op(self):
        rng = np.random.default_rng(22)
        B, L, G = 64, 256, 4
        v = _counters(rng, B, L)
        _pk, dev = _pack_dev(v, min_width=16)
        T, K = 8, 4
        qs = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, op="sum",
                       is_rate=False, dense=True)
        garr = (np.arange(L) % G).astype(np.int32)
        vals, _ = event_topk_grid_packed(dev, 0, qs, 2,
                                         jnp.asarray(garr), G,
                                         interpret=True, largest=False)
        sv = np.asarray(rate_grid_ref(None, jnp.asarray(v[:T + K - 1]),
                                      0, qs))
        gs = np.zeros((G, T))
        for c in range(L):
            gs[garr[c]] += sv[:, c]
        want = np.sort(gs, axis=0)[:2].T
        np.testing.assert_allclose(np.sort(np.asarray(vals), axis=1),
                                   np.sort(want, axis=1), rtol=1e-5)
        with pytest.raises(ValueError, match="filter op"):
            event_topk_grid_packed(dev, 0, qs, 2, jnp.asarray(garr), G,
                                   filt_packed=dev, filt_op="contains",
                                   interpret=True)

    def test_event_topk_group_width_and_segment_paths_agree(self):
        """The three reduce formulations — banded group_width
        reshape-sum, one-hot MXU matmul, and the >_TOPK_ONEHOT_MAX_G
        segment_sum fallback (exercised with a genuinely large group
        space: sparse groups rank NaN) — must rank identically."""
        rng = np.random.default_rng(23)
        B, L, G = 64, 256, 8
        v = _counters(rng, B, L)
        _pk, dev = _pack_dev(v, min_width=16)
        T, K = 8, 4
        qs = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, op="sum",
                       is_rate=False, dense=True)
        garr = (np.arange(L, dtype=np.int32) // (L // G))
        by_onehot = event_topk_grid_packed(
            dev, 0, qs, 3, jnp.asarray(garr), G, interpret=True)
        by_width = event_topk_grid_packed(
            dev, 0, qs, 3, None, G, interpret=True,
            group_width=L // G)
        # same lanes scattered into a 4096-group space (> the one-hot
        # cap -> segment_sum): occupied slots are g*512, so dividing
        # the winning indices by 512 must reproduce the small ranking
        by_segment = event_topk_grid_packed(
            dev, 0, qs, 3, jnp.asarray(garr * 512), 4096,
            interpret=True)
        np.testing.assert_allclose(np.asarray(by_width[0]),
                                   np.asarray(by_onehot[0]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(by_width[1]),
                                      np.asarray(by_onehot[1]))
        np.testing.assert_allclose(np.asarray(by_segment[0]),
                                   np.asarray(by_onehot[0]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(by_segment[1]) // 512,
                                      np.asarray(by_onehot[1]))
        with pytest.raises(ValueError, match="not both"):
            event_topk_grid_packed(dev, 0, qs, 3, jnp.asarray(garr), G,
                                   interpret=True, group_width=L // G)
        with pytest.raises(ValueError, match="group_width"):
            event_topk_grid_packed(dev, 0, qs, 3, None, G + 1,
                                   interpret=True, group_width=L // G)

    def test_banded_mxu_correction_matches_ref(self):
        """K-heavy phase shape (2T < rows) takes the banded one-matmul
        correction+delta path; the reference (roll-scan) oracle pins
        its semantics, counter resets included."""
        rng = np.random.default_rng(15)
        B, L = 64, 256
        v = _counters(rng, B, L)
        # inject counter resets: drop back near the exponent floor
        for lane in range(0, L, 7):
            r = int(rng.integers(5, B - 5))
            v[r:, lane] = v[r:, lane] - v[r, lane] + 2 ** 23
        phase = rng.integers(1, STEP, L).astype(np.int32)
        pk, dev = _pack_dev(v, phase=phase, min_width=16)
        T, K = 8, 40                        # 2T=16 < 47 rows needed
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, is_rate=True,
                      dense=True)
        out = np.asarray(rate_grid_packed(dev, 0, q, row0=0,
                                          interpret=True,
                                          use_phase=True))[:, pk.inv]
        ref = np.asarray(rate_grid_ref(None,
                                       jnp.asarray(v[:T + K - 1]), 0, q,
                                       phase=phase))
        fin = np.isfinite(ref)
        assert (np.isfinite(out) == fin).all()
        np.testing.assert_allclose(out[fin], ref[fin], rtol=2e-5)

    def test_one_executable_serves_every_offset(self, phase_block):
        """``devicestore.series_packed`` takes ``row0`` as an operand:
        three offsets, one of them 8-aligned, run through ONE compiled
        executable, and each answers its own rows."""
        from filodb_tpu.utils.devicewatch import COMPILE_WATCH
        v, phase, pk, dev, want = phase_block
        # a shape no other case compiles, so the first call compiles
        T, K = BLOCK_T - 1, BLOCK_K
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, is_rate=True,
                      dense=True)
        prog = devicestore._fused_progs()["series_packed"]

        def compiles():
            return sum(r["compiles"] for r in COMPILE_WATCH.table()
                       if r["program"] == "devicestore.series_packed")
        before, cached = compiles(), prog._jitted._cache_size()
        for row0 in (3, 40, 17):
            out = np.asarray(prog(dev, 0, q=q, row0=row0, use_phase=True,
                                  interpret=True))[:, pk.inv]
            w = want[row0:row0 + T]
            fin = np.isfinite(w)
            assert (np.isfinite(out) == fin).all()
            np.testing.assert_allclose(out[fin], w[fin], rtol=2e-6)
        assert compiles() == before + 1
        assert prog._jitted._cache_size() == cached + 1


def _hist_plane(rng, B, n_series, hb, mixed=False):
    """[B, n_series*hb] bucket plane: column s*hb + j = series s's
    cumulative bucket j (the devicestore hist group-slot layout), all
    integer-valued with a pinned f32 exponent.  ``mixed`` adds all-NaN
    series and a raw-class (incompressible) series."""
    L = n_series * hb
    start = (2 ** 23 + 128 * rng.integers(0, 2 ** 15, L)).astype(np.float32)
    inc = 128 * rng.integers(1, 8, (B, L))
    v = (start[None, :] + np.cumsum(inc, axis=0)).astype(np.float32)
    if mixed and n_series >= 4:
        v[:, 0:hb] = np.nan                          # dead series
        v[:, hb:2 * hb] = rng.random((B, hb)).astype(np.float32) * 100
    phase = np.repeat(rng.integers(1, STEP, n_series), hb).astype(np.int32)
    return v, phase


class TestHistStridePack:
    """codecs/xorgrid.py stride packs: series-granular classification,
    bucket contiguity, bit-exact roundtrip (ISSUE 14 tentpole 1)."""

    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("hb", [4, 16, 20])
    def test_roundtrip_and_series_contiguity(self, seed, hb):
        rng = np.random.default_rng(seed)
        nser = 37
        v, phase = _hist_plane(rng, 64, nser, hb, mixed=True)
        pk = pack_vals(v, phase=phase, stride=hb)
        if pk is None:
            pytest.skip("mix did not pay at this width")
        np.testing.assert_array_equal(unpack_vals(pk).view(np.uint32),
                                      v.view(np.uint32))
        # every series' hb columns are CONTIGUOUS in packed order, in
        # bucket order — the fused hist kernels' slicing contract
        for s in range(nser):
            pos = pk.inv[s * hb:(s + 1) * hb]
            assert (np.diff(pos) == 1).all(), (s, pos)

    def test_stride_must_divide_width(self):
        rng = np.random.default_rng(1)
        v, _ = _hist_plane(rng, 64, 4, 4)
        with pytest.raises(ValueError, match="stride"):
            pack_vals(v[:, :-1], stride=4)

    def test_stride_alignment_pads_never_split_series(self):
        """Misaligned class widths at stride > 1 must pad (zero lanes),
        never promote a partial series across classes."""
        rng = np.random.default_rng(2)
        hb = 20
        v, phase = _hist_plane(rng, 64, 33, hb, mixed=True)  # 660 cols
        pk = pack_vals(v, phase=phase, stride=hb)
        if pk is None:
            pytest.skip("did not pay")
        for key in ("p8", "p16", "raw"):
            p = pk.planes.get(key)
            if p is None:
                continue
            n = p.shape[1]
            assert n % LANE_BLOCK == 0 or n <= UNPADDED_MAX, (key, n)
        np.testing.assert_array_equal(unpack_vals(pk).view(np.uint32),
                                      v.view(np.uint32))


S_STEP = 60_000
S_T0 = 1_700_000_040_000
S_ROWS = 260            # block 1 (buckets 128..255) full and frozen
S_PANELS = {            # query -> (oracle fn, aggregate, exact)
    'c_total{_ws_="w",_ns_="n"}': ("last", False, True),
    'sum_over_time(c_total{_ws_="w",_ns_="n"}[5m])':
        ("sum_over_time", False, False),
    'rate(c_total{_ws_="w",_ns_="n"}[5m])': ("rate", False, False),
    'sum(rate(c_total{_ws_="w",_ns_="n"}[5m]))': ("rate", True, False),
}


class TestSlidingEndsServed:
    """"Last 20 minutes" panels whose end slides through one block,
    through ``query_range`` on a small store served by the packed
    programs (interpret mode): every end is the oracle's answer, and the
    programs compile once a panel, not once an end."""

    @pytest.fixture(scope="class")
    def served(self):
        from filodb_tpu.coordinator.planner import SingleClusterPlanner
        from filodb_tpu.core.record import RecordBuilder, decode_container
        from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
        from filodb_tpu.core.storeconfig import StoreConfig
        from filodb_tpu.http.server import DatasetBinding, FiloHttpServer
        from filodb_tpu.memstore.memstore import TimeSeriesMemStore
        from filodb_tpu.parallel.shardmap import ShardMapper, ShardStatus
        mp = pytest.MonkeyPatch()
        mp.setattr(devicestore, "_PACKED_INTERPRET", True)
        mp.setattr(devicestore, "_PACKED_BROKEN", False)
        mp.setattr(devicestore.DeviceGridCache, "_val_dtype",
                   lambda self: np.float32)
        ms = TimeSeriesMemStore()
        shard = ms.setup("grid", DEFAULT_SCHEMAS, 0,
                         StoreConfig(device_cache_compress=True))
        rng = np.random.default_rng(7)
        b = RecordBuilder(DEFAULT_SCHEMAS["prom-counter"])
        series = {}
        for i in range(8):
            ph = int(rng.integers(1, S_STEP))
            ts = S_T0 + np.arange(S_ROWS, dtype=np.int64) * S_STEP \
                - S_STEP + ph
            vals = (2 ** 23 + 128 * np.cumsum(
                rng.integers(1, 8, S_ROWS))).astype(np.float64)
            series[f"i{i}"] = (ts, vals)
            b.add_series(ts, [vals], {"__name__": "c_total",
                                      "instance": f"i{i}", "_ws_": "w",
                                      "_ns_": "n"})
        for off, c in enumerate(b.containers()):
            shard.ingest(decode_container(c, DEFAULT_SCHEMAS), off)
        shard.flush_all()
        mapper = ShardMapper(1)
        mapper.register_node([0], "local")
        mapper.update_status(0, ShardStatus.ACTIVE)
        srv = FiloHttpServer()
        srv.bind_dataset(DatasetBinding(
            "grid", ms, SingleClusterPlanner("grid", mapper,
                                             DatasetOptions(),
                                             spread_default=0)))
        port = srv.start()
        yield port, shard, series
        srv.shutdown()
        mp.undo()

    def test_every_end_is_the_oracles_and_compiles_once(self, served):
        import json
        import time
        import urllib.parse
        import urllib.request

        from filodb_tpu.utils.devicewatch import COMPILE_WATCH
        from filodb_tpu.utils.observability import TRACER
        port, shard, series = served
        T, W = 20, 300_000

        def compiles():
            return {r["program"]: r["compiles"]
                    for r in COMPILE_WATCH.table()
                    if r["program"].endswith("_packed")}

        def packed_spans():
            return TRACER.stages.snapshot().get(
                "grid.packed", {"count": 0})["count"]
        before, spans0 = compiles(), packed_spans()
        # step k stands at bucket k + 1 of the cache: an end at step
        # 150 + r puts the 24-row span at row r of block 1, r = 0..104
        offsets = (0, 1, 7, 8, 9, 31, 64, 99, 104)
        served_at = set()
        for r in offsets:
            end = S_T0 + (150 + r) * S_STEP
            start = end - (T - 1) * S_STEP
            for query, (fn, agg, exact) in S_PANELS.items():
                qs = urllib.parse.urlencode(dict(
                    query=query, start=start / 1000, end=end / 1000,
                    step="60s"))
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/promql/grid/api/v1/"
                        f"query_range?{qs}", timeout=60) as resp:
                    result = json.loads(resp.read())["data"]["result"]
                want = {k: oracle.range_fn(fn, ts, vals, start, end,
                                           S_STEP, W)
                        for k, (ts, vals) in series.items()}
                if agg:
                    want = {"": np.sum(list(want.values()), axis=0)}
                got = {e["metric"].get("instance", ""):
                       np.array([float(v) for _t, v in e["values"]])
                       for e in result}
                assert got.keys() == want.keys(), (query, r)
                for k, w in want.items():
                    assert np.isfinite(w).all()
                    if exact:
                        np.testing.assert_array_equal(got[k], w)
                    else:
                        np.testing.assert_allclose(got[k], w, rtol=2e-6,
                                                   err_msg=f"{query} {r}")
            cache = next(iter(shard.device_caches.values()))
            served_at |= {p.packed_row0 for p in cache._plan_memo.values()
                          if p.packed is not None}
        assert served_at == set(offsets)
        asked = len(offsets) * len(S_PANELS)
        for _ in range(100):      # the worker folds its stages in last
            if packed_spans() - spans0 >= asked:
                break
            time.sleep(0.02)
        assert packed_spans() - spans0 == asked
        # one compile a panel shape at most, whatever the ends
        grew = {p: n - before.get(p, 0) for p, n in compiles().items()}
        assert grew.get("devicestore.series_packed", 0) <= 3, grew
        assert grew.get("devicestore.grouped_packed", 0) <= 1, grew


class TestHistFusedKernels:
    """hist_grid_grouped_packed in interpret mode vs the decoded-plane
    reference, alone and under the hist-quantile interpolation
    (ISSUE 14 tentpole 2)."""

    @pytest.mark.parametrize("hb,row0", [(4, 0), (8, 3), (20, 0)])
    def test_grouped_matches_ref(self, hb, row0):
        rng = np.random.default_rng(31)
        per, gh = 8, 4
        nser = per * gh
        v, phase = _hist_plane(rng, 64, nser, hb)
        v[:, 2 * hb:3 * hb] = np.nan               # one dead series
        pk = pack_vals(v, phase=phase, min_width=16, stride=hb)
        assert pk is not None and (pk.inv == np.arange(nser * hb)).all()
        dev = {k: jnp.asarray(a) for k, a in pk.planes.items()}
        T, K = 10, 5
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, is_rate=True,
                      dense=True)
        s, c = hist_grid_grouped_packed(dev, 0, q, hb,
                                        group_lanes=per * hb, row0=row0,
                                        interpret=True, use_phase=True)
        s, c = np.asarray(s), np.asarray(c)
        assert s.shape == (gh * hb, T)
        ref = np.asarray(rate_grid_ref(
            None, jnp.asarray(v[row0:row0 + T + K - 1]), 0, q,
            phase=phase))
        want = np.zeros((gh * hb, T), np.float32)
        wcnt = np.zeros((gh * hb, T), np.float32)
        for col in range(nser * hb):
            g, j = col // (per * hb), col % hb
            fin = np.isfinite(ref[:, col])
            want[g * hb + j] += np.where(fin, ref[:, col], 0.0)
            wcnt[g * hb + j] += fin
        np.testing.assert_allclose(s, want, rtol=2e-5)
        np.testing.assert_array_equal(c, wcnt)

    def test_quantile_matches_numpy(self):
        rng = np.random.default_rng(32)
        hb, per, gh = 8, 16, 4
        v, phase = _hist_plane(rng, 64, per * gh, hb)
        pk = pack_vals(v, phase=phase, min_width=16, stride=hb)
        assert pk is not None
        dev = {k: jnp.asarray(a) for k, a in pk.planes.items()}
        T, K = 10, 5
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, is_rate=True,
                      dense=True)
        tops = np.concatenate([2.0 ** np.arange(hb - 1), [np.inf]])
        s, c = hist_grid_grouped_packed(dev, 0, q, hb,
                                        group_lanes=per * hb,
                                        interpret=True, use_phase=True)
        hist_sum, count = devicestore.hist_planes_split(
            jnp.stack([s, c]), gh, hb)
        out = np.asarray(histogram_ops.hist_quantile(
            jnp.asarray(tops), hist_sum, 0.99))
        assert (np.asarray(count) == per).all()
        # NumPy all the way: per-column rates, a per-lane bucket reduce
        # (slot g*hb + j), the host's histogram_quantile
        ref = np.asarray(rate_grid_ref(None, jnp.asarray(v[:T + K - 1]),
                                       0, q, phase=phase))
        cols = np.arange(per * gh * hb)
        slots = (cols // (per * hb)) * hb + cols % hb
        rows = oracle.grouped_reduce(ref, slots, gh * hb, "sum")[0]
        rows = rows.reshape(gh, hb, T).transpose(0, 2, 1)
        want = quantile_bulk(tops, rows.reshape(gh * T, hb),
                             0.99).reshape(gh, T)
        # the interpolation's divides amplify the f32 sums' last ulps
        np.testing.assert_allclose(out, want, rtol=2e-4)

    def test_free_op_sum_over_time_no_phase(self):
        """TS_FREE hist shape (sum_over_time over buckets) takes the
        non-phase kernel branch."""
        rng = np.random.default_rng(33)
        hb, per, gh = 4, 8, 2
        v, phase = _hist_plane(rng, 64, per * gh, hb)
        pk = pack_vals(v, phase=phase, min_width=16, stride=hb)
        dev = {k: jnp.asarray(a) for k, a in pk.planes.items()}
        T, K = 10, 4
        q = GridQuery(nsteps=T, kbuckets=K, gstep_ms=STEP, op="sum",
                      is_rate=False, dense=True)
        s, c = hist_grid_grouped_packed(dev, 0, q, hb,
                                        group_lanes=per * hb,
                                        interpret=True, use_phase=False)
        ref = np.asarray(rate_grid_ref(None, jnp.asarray(v[:T + K - 1]),
                                       0, q))
        want = np.zeros((gh * hb, T), np.float32)
        for col in range(per * gh * hb):
            g, j = col // (per * hb), col % hb
            want[g * hb + j] += np.where(np.isfinite(ref[:, col]),
                                         ref[:, col], 0.0)
        np.testing.assert_allclose(np.asarray(s), want, rtol=2e-5)

    def test_rejects_misaligned_and_padded(self):
        rng = np.random.default_rng(34)
        hb = 4
        v, phase = _hist_plane(rng, 64, 32, hb)
        pk = pack_vals(v, phase=phase, min_width=16, stride=hb)
        dev = {k: jnp.asarray(a) for k, a in pk.planes.items()}
        q = GridQuery(nsteps=8, kbuckets=4, gstep_ms=STEP, dense=True)
        with pytest.raises(ValueError, match="multiple of"):
            hist_grid_grouped_packed(dev, 0, q, hb, group_lanes=30,
                                     interpret=True)
        padded = dict(pk.planes)
        padded["p16"] = np.pad(padded["p16"], ((0, 0), (0, 128)))
        padded["m16"] = np.pad(padded["m16"], ((0, 0), (0, 128)))
        padded["z16"] = np.pad(padded["z16"], (0, 128))
        padded["first"] = np.pad(padded["first"], (0, 128))
        devp = {k: jnp.asarray(a) for k, a in padded.items()}
        with pytest.raises(ValueError, match="pad lanes"):
            hist_grid_grouped_packed(devp, 0, q, hb, group_lanes=32,
                                     interpret=True)


class TestHistServingWidening:
    """Mid-stream bucket-count widening (16 -> 20 buckets) through the
    REAL serving path (devicestore.py hb re-probe): the cache disables
    on the widened chunk, re-probes the bucket scheme, and the packed
    fused path serves the widened layout with narrow rows edge-padded —
    equal to the host oracle."""

    def test_widening_16_to_20_reprobes_and_serves_packed(self, monkeypatch):
        from filodb_tpu.codecs import histcodec
        from filodb_tpu.core.filters import ColumnFilter, Equals
        from filodb_tpu.core.histogram import GeometricBuckets
        from filodb_tpu.core.record import RecordBuilder, decode_container
        from filodb_tpu.core.schemas import DEFAULT_SCHEMAS, DatasetOptions
        from filodb_tpu.core.storeconfig import StoreConfig
        from filodb_tpu.memstore import devicestore
        from filodb_tpu.memstore.memstore import TimeSeriesMemStore
        from filodb_tpu.ops.windows import StepRange
        from filodb_tpu.query import rangefns
        from filodb_tpu.query.logical import RangeFunctionId as F

        monkeypatch.setattr(devicestore, "_PACKED_INTERPRET", True)
        monkeypatch.setattr(devicestore, "_PACKED_BROKEN", False)
        monkeypatch.setattr(devicestore.DeviceGridCache, "_val_dtype",
                            lambda self: np.float32)
        T0 = 1_600_000_000_000
        HSTEP = 10_000
        rng = np.random.default_rng(6)
        ms = TimeSeriesMemStore()
        shard = ms.setup("prom", DEFAULT_SCHEMAS, 0, StoreConfig())

        def ingest(t0, rows, nb, off0):
            buckets = GeometricBuckets(2.0, 2.0, nb)
            b = RecordBuilder(DEFAULT_SCHEMAS["prom-histogram"],
                              DatasetOptions())
            for s in range(3):
                cum = np.zeros(nb, np.int64)
                for t in range(rows):
                    cum += 128 * rng.integers(1, 8, nb)
                    vals = 2 ** 23 + np.cumsum(cum)
                    blob = histcodec.encode_hist_value(buckets, vals)
                    b.add(t0 + t * HSTEP, (float(vals[-1]),
                                           float(vals[-1]), blob),
                          {"__name__": "lat", "inst": f"i{s}",
                           "_ws_": "w", "_ns_": "n"})
            for off, c in enumerate(b.containers()):
                shard.ingest(decode_container(c, DEFAULT_SCHEMAS),
                             off0 + off)
            shard.flush_all()

        ingest(T0, 48, 16, 0)
        res = shard.lookup_partitions(
            [ColumnFilter("_metric_", Equals("lat"))], 0, 2 ** 62)
        K = 4
        W = K * HSTEP
        steps0 = T0 + (K + 1) * HSTEP
        got = shard.scan_grid(res.part_ids, F.SUM_OVER_TIME, steps0, 20,
                              HSTEP, W)
        assert got is not None
        cache = next(iter(shard.device_caches.values()))
        assert cache.hb == 16
        assert next(iter(cache._plan_memo.values())).packed is not None
        # widen mid-stream: 20-bucket rows arrive
        ingest(T0 + 48 * HSTEP, 48, 20, 100)
        # the first query over the widened span hits the 16-bucket probe
        # and disables (devicestore _build: bucket scheme widened); the
        # re-probe path must then serve hb=20 once the backoff clears
        steps1 = T0 + (48 + K + 1) * HSTEP
        shard.scan_grid(res.part_ids, F.SUM_OVER_TIME, steps1, 20,
                        HSTEP, W)
        cache.disabled_until_version = -1          # clear the backoff
        got2 = shard.scan_grid(res.part_ids, F.SUM_OVER_TIME, steps1, 20,
                               HSTEP, W)
        assert got2 is not None
        assert cache.hb == 20
        tags, vals, tops = got2
        assert vals.shape[2] == 20 and len(tops) == 20
        plan = next(iter(cache._plan_memo.values()))
        assert plan.packed is not None, "widened hist did not re-pack"
        assert not devicestore._PACKED_BROKEN
        # host oracle over the widened span
        end = steps1 + 19 * HSTEP
        t2, batch = shard.scan_batch(res.part_ids, steps1 - W, end)
        sr = StepRange(steps1, end, HSTEP)
        want = np.asarray(rangefns.apply_range_function(
            batch, sr, W, F.SUM_OVER_TIME))[:len(tags)]
        fin = np.isfinite(want)
        assert (np.isfinite(np.asarray(vals)) == fin).all()
        np.testing.assert_allclose(np.asarray(vals)[fin], want[fin],
                                   rtol=1e-5)
