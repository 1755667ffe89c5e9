"""Device-resident zero-copy datapath: bit-identity with the legacy path.

The arena-staged, donated-encode group datapath must leave
*exactly* the media, OOB, write pointers, L2P and validity state the
per-block/per-stripe legacy path produces -- across schemes, for healthy
reads, degraded reads on every surviving-role set, rebuild, and GC.  A
group is on the media when the write that filled it returns.  The
vectorized L2P batch ops are property-tested against the scalar reference.
See DESIGN.md §9.
"""
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.array import ZapRaidConfig, ZapRAIDArray
from repro.core.l2p import NO_PBA, L2PTable, pack_pba, pack_pba_many, unpack_pba
from repro.core.zns import DeviceCrashed, ZnsConfig

BB = 256
SCHEMES = [("raid4", 4), ("raid5", 4), ("raid6", 5), ("raid01", 4)]


def _mk(batched, scheme="raid5", n_drives=4, **kw):
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=8,
                        chunk_blocks=1, logical_blocks=256,
                        gc_free_segments_low=1, batched=batched, **kw)
    zns = ZnsConfig(n_zones=12, zone_cap_blocks=64, block_bytes=BB)
    return ZapRAIDArray(cfg, zns)


def _workload(arr, seed=3, n_writes=200, flush_every=0):
    """Mixed-size random writes; optional mid-stream flushes exercise the
    partial-group pad-in-place path.  Returns the logical reference image."""
    rng = np.random.default_rng(seed)
    ref = {}
    for i in range(n_writes):
        n = int(rng.integers(1, 4))
        lba = int(rng.integers(0, 256 - n))
        blk = rng.integers(0, 256, (n, BB), dtype=np.uint8)
        arr.write(lba, blk)
        for j in range(n):
            ref[lba + j] = blk[j].copy()
        if flush_every and (i + 1) % flush_every == 0:
            arr.flush()
    arr.flush()
    return ref


def _assert_media_equal(a1, a0):
    for d1, d0 in zip(a1.drives, a0.drives):
        assert np.array_equal(d1.data, d0.data)
        assert np.array_equal(d1.oob, d0.oob)
        assert np.array_equal(d1.wp, d0.wp)


# ----------------------------------------------------- write-path identity

@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_device_resident_media_identical_to_legacy(scheme, n_drives):
    a1 = _mk(True, scheme, n_drives)
    a0 = _mk(False, scheme, n_drives)
    r1 = _workload(a1)
    r0 = _workload(a0)
    assert r1.keys() == r0.keys()
    _assert_media_equal(a1, a0)


@pytest.mark.parametrize("scheme,n_drives", [("raid5", 4), ("raid6", 5)])
def test_partial_group_flush_identical(scheme, n_drives):
    """Frequent flushes: pad-in-place partial groups, every pow2 bucket."""
    a1 = _mk(True, scheme, n_drives)
    a0 = _mk(False, scheme, n_drives)
    _workload(a1, seed=7, n_writes=120, flush_every=5)
    _workload(a0, seed=7, n_writes=120, flush_every=5)
    _assert_media_equal(a1, a0)
    assert a1.stats.padded_blocks == a0.stats.padded_blocks


def test_filled_group_is_on_media_when_write_returns():
    """A write that fills a stripe group commits it before returning: the
    L2P maps every block, each member zone's write pointer has moved past
    the group, and nothing is left in volatile staging."""
    arr = _mk(True)
    rng = np.random.default_rng(5)
    blk = rng.integers(0, 256, (3 * 8, BB), dtype=np.uint8)  # k*G: one group
    arr.write(0, blk)
    assert not arr.has_staged()
    assert all(arr.l2p.get(lba) != NO_PBA for lba in range(3 * 8))
    (ost,) = arr.open_segments.values()
    info = ost.info
    assert info.stripes_written == 8
    for d, zone in zip(info.drive_ids, info.zone_ids):
        assert int(arr.drives[d].wp[zone]) == info.data_start() + 8
    assert np.array_equal(arr.read(0, 3 * 8), blk)


def test_partial_stripe_stays_staged_until_flush():
    """Blocks short of a full stripe are volatile until ``flush()`` pads
    and commits them."""
    arr = _mk(True)
    blk = np.random.default_rng(8).integers(0, 256, (2, BB), dtype=np.uint8)
    arr.write(0, blk)
    assert arr.has_staged()
    assert arr.l2p.get(0) == NO_PBA
    arr.flush()
    assert not arr.has_staged()
    assert arr.l2p.get(0) != NO_PBA
    assert np.array_equal(arr.read(0, 2), blk)


def test_arm_crash_after_completed_group_keeps_it_readable():
    """A group committed before ``arm_crash(0)`` is untouched by the
    budget; the write that fills the next group hits it, in the per-command
    commit loop, and the first group still reads back after disarming."""
    arr = _mk(True)
    rng = np.random.default_rng(6)
    first = rng.integers(0, 256, (3 * 8, BB), dtype=np.uint8)
    arr.write(0, first)
    arr.arm_crash(0)
    with pytest.raises(DeviceCrashed):
        arr.write(3 * 8, rng.integers(0, 256, (3 * 8, BB), dtype=np.uint8))
    arr.disarm_crash()
    assert np.array_equal(arr.read(0, 3 * 8), first)


# ------------------------------------------------------ read-path identity

@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_degraded_reads_every_surviving_role_set(scheme, n_drives):
    """Fail each drive in turn: with parity rotation every failure exercises
    a different mix of surviving-role sets through the fused decode."""
    a1 = _mk(True, scheme, n_drives)
    ref = _workload(a1, seed=13)
    lbas = sorted(ref)
    want = np.stack([ref[l] for l in lbas])
    for failed in range(n_drives):
        a1.drives[failed].failed = True
        got = np.stack([a1.read(l, 1)[0] for l in lbas])       # scalar path
        assert np.array_equal(got, want), (scheme, failed)
        got_b = a1.read(0, 256)                                # batched path
        for i, l in enumerate(lbas):
            assert np.array_equal(got_b[l], ref[l]), (scheme, failed, l)
        a1.drives[failed].failed = False


@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_rebuild_identical_to_legacy(scheme, n_drives):
    a1 = _mk(True, scheme, n_drives)
    a0 = _mk(False, scheme, n_drives)
    ref = _workload(a1, seed=17)
    _workload(a0, seed=17)
    for a in (a1, a0):
        a.fail_drive(1)
        a.rebuild_drive(1)
    _assert_media_equal(a1, a0)
    for lba, want in ref.items():
        assert np.array_equal(a1.read(lba, 1)[0], want)


def test_gc_identical_to_legacy():
    """Overwrite-heavy workload forces GC in both modes -> same media."""
    def run(batched):
        cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8,
                            chunk_blocks=1, logical_blocks=96,
                            gc_free_segments_low=2, batched=batched)
        zns = ZnsConfig(n_zones=6, zone_cap_blocks=64, block_bytes=BB)
        arr = ZapRAIDArray(cfg, zns)
        rng = np.random.default_rng(19)
        ref = {}
        for _ in range(900):
            lba = int(rng.integers(0, 96))
            blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
            arr.write(lba, blk)
            ref[lba] = blk[0].copy()
        arr.flush()
        return arr, ref

    a1, r1 = run(True)
    a0, r0 = run(False)
    assert a1.stats.gc_runs > 0 and a1.stats.gc_runs == a0.stats.gc_runs
    _assert_media_equal(a1, a0)
    for lba, want in r1.items():
        assert np.array_equal(a1.read(lba, 1)[0], want)


def test_copy_counters_count_groups_not_stripes():
    """The device-resident path's transfer count scales with *groups*."""
    arr = _mk(True)
    rng = np.random.default_rng(23)
    arr.write(0, rng.integers(0, 256, (3 * 8 * 4, BB), dtype=np.uint8))
    arr.flush()
    groups = arr.stats.stripes_committed / arr.cfg.group_size
    # payload encode + OOB-meta encode per group, nothing per stripe
    assert arr.stats.h2d_copies <= 2 * groups + 2
    assert arr.stats.h2d_bytes > 0 and arr.stats.d2h_bytes > 0


def test_timed_pipeline_reports_encode_sync(host_spans):
    """The timed pipeline's group commits each wait on their encodes once:
    one ``codec:wait`` host span for the payload parity and one for the
    metadata parity of every group built."""
    from repro.core.handlers import HandlerPipeline
    from repro.sim import Request

    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8,
                        chunk_blocks=1, logical_blocks=256,
                        gc_free_segments_low=1)
    zns = ZnsConfig(n_zones=12, zone_cap_blocks=64, block_bytes=BB)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=3)
    rng = np.random.default_rng(29)
    reqs = [Request(float(i) * 10.0, "t", "W", int(rng.integers(0, 250)), 1)
            for i in range(64)]
    pipe.replay(reqs, payload_fn=lambda r: rng.integers(
        0, 256, (r.n_blocks, BB), dtype=np.uint8))
    spans = host_spans.snapshot()["spans"]
    groups = spans["array:build"]["count"]
    assert groups >= 1                                    # groups encoded
    assert spans["codec:wait"]["count"] == 2 * groups
    assert spans["codec:wait"]["self_s"] >= 0.0


# ------------------------------------------------------- L2P property test

@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=120), st.randoms())
def test_l2p_batch_ops_match_scalar_reference(limit, rnd):
    """get_many/set_many (bitmap CLOCK) vs a scalar get/set shadow table."""
    written_v, written_s = {}, {}

    def mk(store):
        return L2PTable(
            480, memory_limit_entries=limit,
            write_mapping_block=lambda g, e: store.__setitem__(g, e.copy()),
            read_mapping_block=lambda g: store.get(g),
            entries_per_group=32,
        )

    vec, ref = mk(written_v), mk(written_s)
    for _ in range(30):
        n = rnd.randint(1, 24)
        lbas = np.array([rnd.randrange(480) for _ in range(n)], dtype=np.int64)
        if rnd.random() < 0.6:
            pbas = np.array(
                [pack_pba(rnd.randrange(64), rnd.randrange(4), rnd.randrange(100))
                 for _ in range(n)], dtype=np.int64)
            vec.set_many(lbas, pbas)
            for l, p in zip(lbas, pbas):  # scalar shadow, same order
                ref.set(int(l), int(p))
        else:
            got = vec.get_many(lbas)
            want = np.array([ref.get(int(l)) for l in lbas])
            assert np.array_equal(got, want)
    vec.flush()
    ref.flush()
    final_v = vec.get_many(np.arange(480))
    final_s = np.array([ref.get(i) for i in range(480)])
    assert np.array_equal(final_v, final_s)
    assert vec.memory_bytes() == len(vec.resident) * 32 * 4  # accounting exact


def test_pack_pba_many_matches_scalar():
    drv = np.array([0, 3, 15])
    off = np.array([0, 77, 65535])
    got = pack_pba_many(9, drv, off)
    for i in range(3):
        assert int(got[i]) == pack_pba(9, int(drv[i]), int(off[i]))
        assert unpack_pba(int(got[i])) == (9, int(drv[i]), int(off[i]))
