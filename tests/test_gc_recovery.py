"""Zero-copy background services: bit-identity of the batched GC and
crash-recovery pipelines with their scalar baselines.

The vectorized GC pipeline (cost-benefit victim scoring, one gather + OOB
read per drive, mask-resolved liveness, bulk arena restage) and the batched
recovery scanner (cross-zone header gather, vectorized stripe scan, lexsort
winner resolution, bulk L2P/validity install) must leave *exactly* the
media, OOB, write pointers, L2P, validity and stats the per-block scalar
paths produce -- across schemes, with mapping blocks in flight, with a
failed drive, and after a crash armed mid-GC.  See DESIGN.md §10.
"""
import numpy as np
import pytest

from repro.core.array import ZapRaidConfig, ZapRAIDArray
from repro.core.recovery import recover_array
from repro.core.segment import SegmentState
from repro.core.zns import DeviceCrashed, ZnsConfig

BB = 256
SCHEMES = [("raid4", 4), ("raid5", 4), ("raid6", 5), ("raid01", 4)]


def _mk(batched, scheme="raid5", n_drives=4, zones=6, logical=600, **kw):
    kw.setdefault("gc_free_segments_low", 2)
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=8,
                        chunk_blocks=1, logical_blocks=logical,
                        batched=batched, **kw)
    zns = ZnsConfig(n_zones=zones, zone_cap_blocks=64, block_bytes=BB)
    return ZapRAIDArray(cfg, zns), cfg, zns


def _churn(arr, n_writes, logical, seed=7):
    """Sequential wrap-around overwrites: victims keep partial liveness, so
    GC genuinely moves blocks (not just reclaims fully-stale segments)."""
    rng = np.random.default_rng(seed)
    ref = {}
    for i in range(n_writes):
        lba = i % logical
        blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        arr.write(lba, blk)
        ref[lba] = blk[0].copy()
    arr.flush()
    return ref


def _assert_state_identical(a1, a0):
    for d1, d0 in zip(a1.drives, a0.drives):
        assert np.array_equal(d1.data, d0.data)
        assert np.array_equal(d1.oob, d0.oob)
        assert np.array_equal(d1.wp, d0.wp)
    assert set(a1.segments) == set(a0.segments)
    for sid in a1.segments:
        assert np.array_equal(a1.segments[sid].valid, a0.segments[sid].valid)
        assert a1.segments[sid].valid_count == a0.segments[sid].valid_count
    if not a1.l2p.offload:
        assert np.array_equal(a1.l2p.flat, a0.l2p.flat)
    else:
        assert sorted(a1.l2p.resident) == sorted(a0.l2p.resident)
        for g in a1.l2p.resident:
            assert np.array_equal(a1.l2p.resident[g], a0.l2p.resident[g])
    assert a1.mapping_table == a0.mapping_table
    s1, s0 = a1.stats, a0.stats
    for f in ("host_blocks_written", "device_blocks_written",
              "stripes_committed", "padded_blocks", "gc_runs",
              "gc_blocks_moved", "meta_blocks_written", "degraded_reads",
              "recovery_blocks_read"):
        assert getattr(s1, f) == getattr(s0, f), f
    assert a1.ts_counter == a0.ts_counter


# ------------------------------------------------------------ GC identity

@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_gc_pipeline_identical_to_scalar(scheme, n_drives):
    """GC with real moves: batched collection + bulk restage vs the scalar
    per-block baseline leave identical media/OOB/L2P/validity/stats."""
    logical = 360 if scheme == "raid01" else 600
    n_writes = 1000 if scheme == "raid01" else 1400
    a1, *_ = _mk(True, scheme, n_drives, logical=logical)
    a0, *_ = _mk(False, scheme, n_drives, logical=logical)
    r1 = _churn(a1, n_writes, logical)
    r0 = _churn(a0, n_writes, logical)
    assert a1.stats.gc_runs > 0
    assert a1.stats.gc_blocks_moved > 0  # victims were partially live
    _assert_state_identical(a1, a0)
    for lba, want in r1.items():
        assert np.array_equal(a1.read(lba, 1)[0], want)
    assert r1.keys() == r0.keys()


@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_gc_degraded_collection_identical(scheme, n_drives):
    """With a failed drive, batched collection reconstructs the lost chunks
    through the fused whole-chunk decode and yields exactly the scalar
    per-block degraded-read collection (payloads, LBAs, order)."""
    logical = 360 if scheme == "raid01" else 600
    a1, *_ = _mk(True, scheme, n_drives, logical=logical,
                 gc_free_segments_low=0)  # no auto-GC: inspect collection
    a0, *_ = _mk(False, scheme, n_drives, logical=logical,
                 gc_free_segments_low=0)
    _churn(a1, logical + 200, logical)
    _churn(a0, logical + 200, logical)
    sealed1 = sorted(
        (r for r in a1.segments.values()
         if r.info.state == int(SegmentState.SEALED)),
        key=lambda r: r.info.seg_id,
    )
    sealed0 = sorted(
        (r for r in a0.segments.values()
         if r.info.state == int(SegmentState.SEALED)),
        key=lambda r: r.info.seg_id,
    )
    assert sealed1 and len(sealed1) == len(sealed0)
    moved_any = False
    for failed in range(n_drives):
        a1.drives[failed].failed = True
        a0.drives[failed].failed = True
        for rec1, rec0 in zip(sealed1, sealed0):
            got = a1._gc_collect_batched(rec1)
            want = a0._gc_collect_scalar(rec0)
            for g, w in zip(got, want):
                assert np.array_equal(g, w), (scheme, failed)
            moved_any = moved_any or got[0].size > 0
        a1.drives[failed].failed = False
        a0.drives[failed].failed = False
    assert moved_any  # the comparison exercised live blocks


def test_gc_moves_mapping_blocks_batched():
    """Satellite: mapping blocks (L2P offload) restage through the bulk
    append path under ``batched=True`` and stay bit-identical to scalar."""
    from repro.core.l2p import unpack_pba

    def run(batched):
        arr, *_ = _mk(batched, zones=12, logical=600,
                      gc_free_segments_low=0,
                      l2p_memory_limit_entries=128)
        rng = np.random.default_rng(7)
        for lba in range(600):  # fill every entry group once
            arr.write(lba, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        arr.flush()
        for i in range(700):    # then churn only a hot range: the cold
            # groups' mapping blocks stay live inside future GC victims
            arr.write(i % 128, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        arr.flush()
        meta_moved = 0
        for _ in range(4):
            victim = arr._gc_select_victim()
            if victim is None:
                break
            vid = victim.info.seg_id
            meta_moved += sum(
                1 for p in arr.mapping_table.values() if unpack_pba(p)[0] == vid
            )
            if not arr.gc_once():
                break
        return arr, meta_moved

    a1, m1 = run(True)
    a0, m0 = run(False)
    assert a1.stats.gc_runs > 0
    assert m1 > 0 and m1 == m0  # live mapping blocks actually moved
    _assert_state_identical(a1, a0)


def test_gc_cost_benefit_prefers_stale_victims():
    """The vectorized scorer picks a (mostly) stale victim over a hot one."""
    arr, *_ = _mk(True, zones=8, logical=600, gc_free_segments_low=0)
    _churn(arr, 1100, 600)
    sealed = [r for r in arr.segments.values()
              if r.info.state == int(SegmentState.SEALED)]
    assert len(sealed) >= 2
    victim = arr._gc_select_victim()
    assert victim is not None
    # no sealed segment with a strictly better (lower) utilization at
    # comparable-or-greater age should have been passed over entirely
    u_victim = victim.valid_count / victim.data_capacity()
    assert u_victim < 1.0
    fullest = max(sealed, key=lambda r: r.valid_count / r.data_capacity())
    if fullest.valid_count < fullest.data_capacity():
        u_full = fullest.valid_count / fullest.data_capacity()
        assert u_victim <= u_full + 1e-9


# ------------------------------------------------------ recovery identity

@pytest.mark.parametrize("scheme,n_drives", [("raid5", 4), ("raid6", 5)])
def test_recovery_scan_identical_to_scalar(scheme, n_drives):
    """Clean-shutdown recovery: batched header/OOB scans + lexsort winners +
    bulk install reproduce the scalar recovery bit for bit."""
    def run(batched):
        arr, cfg, zns = _mk(batched, scheme, n_drives)
        ref = _churn(arr, 1400, 600)
        arr2 = recover_array(arr.drives, cfg, zns)
        return arr2, ref

    a1, r1 = run(True)
    a0, r0 = run(False)
    _assert_state_identical(a1, a0)
    for lba, want in r1.items():
        assert np.array_equal(a1.read(lba, 1)[0], want)


def test_recovery_open_segment_scan_identical():
    """Stop mid-segment (no flush-to-seal): the OOB scan path dominates and
    must agree across modes, including stripes_written cursors."""
    def run(batched):
        arr, cfg, zns = _mk(batched, zones=8)
        rng = np.random.default_rng(23)
        for i in range(150):  # well short of sealing
            arr.write(i % 600, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        arr.flush()
        return recover_array(arr.drives, cfg, zns), arr

    a1, _ = run(True)
    a0, _ = run(False)
    _assert_state_identical(a1, a0)
    for sid, ost in a1.open_segments.items():
        assert ost.info.stripes_written == \
            a0.open_segments[sid].info.stripes_written


def test_recovery_after_crash_identical():
    """Crash mid-workload: both modes recover to identical state."""
    def run(batched):
        arr, cfg, zns = _mk(batched)
        rng = np.random.default_rng(7)
        for i in range(1400):
            blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
            if i == 1180:
                arr.arm_crash(40)
            try:
                arr.write(i % 600, blk)
            except DeviceCrashed:
                break
        return recover_array(arr.drives, cfg, zns)

    a1 = run(True)
    a0 = run(False)
    _assert_state_identical(a1, a0)


def test_recovery_after_crash_armed_mid_gc():
    """The crash budget bites during a GC restage: half-moved survivors, a
    victim segment not yet reclaimed.  Recovery must converge to identical
    state in both modes and stay writable."""
    def run(batched):
        arr, cfg, zns = _mk(batched)
        crashed_in_gc = [False]
        orig = arr.gc_once

        def traced():
            try:
                return orig()
            except DeviceCrashed:
                crashed_in_gc[0] = True
                raise

        arr.gc_once = traced
        rng = np.random.default_rng(7)
        for i in range(1400):
            blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
            if i == 694:  # the next write triggers GC (seeded workload)
                arr.arm_crash(100)
            try:
                arr.write(i % 600, blk)
            except DeviceCrashed:
                break
        assert crashed_in_gc[0], "crash point must land inside gc_once"
        return recover_array(arr.drives, cfg, zns)

    a1 = run(True)
    a0 = run(False)
    _assert_state_identical(a1, a0)
    # still writable post-recovery
    blk = np.random.default_rng(1).integers(0, 256, (1, BB), dtype=np.uint8)
    a1.write(3, blk)
    a1.flush()
    assert np.array_equal(a1.read(3, 1)[0], blk[0])


def test_recovery_offload_identical():
    """L2P offload: mapping-block winners, stay-offloaded groups and the
    resident set agree across modes."""
    def run(batched):
        arr, cfg, zns = _mk(batched, zones=24, logical=512,
                            gc_free_segments_low=1,
                            l2p_memory_limit_entries=128)
        rng = np.random.default_rng(4)
        ref = {}
        for _ in range(800):
            lba = int(rng.integers(0, 512))
            blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
            arr.write(lba, blk)
            ref[lba] = blk[0].copy()
        arr.flush()
        return recover_array(arr.drives, cfg, zns), ref

    a1, r1 = run(True)
    a0, r0 = run(False)
    _assert_state_identical(a1, a0)
    for lba, want in r1.items():
        assert np.array_equal(a1.read(lba, 1)[0], want)


# --------------------------------------------- rebuild satellites / actors

def test_rebuild_scalar_open_segment_path():
    """Non-batched rebuild fallback covers the open-segment n_chunks path
    (per-chunk loop + hoisted scaffolding) and matches the batched rebuild."""
    def run(batched):
        arr, *_ = _mk(batched, zones=8)
        rng = np.random.default_rng(29)
        for i in range(220):  # leaves an open segment with stripes written
            arr.write(i % 600, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        arr.flush()
        ost = next(iter(arr.open_segments.values()))
        assert 0 < ost.info.stripes_written < ost.info.n_stripes
        arr.fail_drive(1)
        arr.rebuild_drive(1)
        return arr

    a1 = run(True)
    a0 = run(False)
    for d1, d0 in zip(a1.drives, a0.drives):
        assert np.array_equal(d1.data, d0.data)
        assert np.array_equal(d1.oob, d0.oob)
        assert np.array_equal(d1.wp, d0.wp)


def _gc_pressure_pipe():
    """A timed raid5 array aged to its GC watermark (900 writes over 360
    LBAs on 7 zones)."""
    from repro.core.handlers import HandlerPipeline

    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8,
                        chunk_blocks=1, logical_blocks=360,
                        gc_free_segments_low=1)
    zns = ZnsConfig(n_zones=7, zone_cap_blocks=64, block_bytes=BB)
    rng = np.random.default_rng(11)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=11)
    pipe.precondition(
        (i % 360, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        for i in range(900)
    )
    return pipe


def _gc_pressure_load(n_writes, n_reads):
    from repro.sim import TenantSpec, multi_tenant

    return multi_tenant([
        TenantSpec(name="writer", kind="seq", n_ops=n_writes,
                   rate_iops=50_000, seed=41),
        TenantSpec(name="reader", kind="uniform", n_ops=n_reads,
                   rate_iops=20_000, read_frac=1.0, seed=42),
    ], logical_blocks=360)


def test_timed_gc_actor_paces_background_cleaning():
    """The rate-limited GC actor cleans proactively on the virtual timeline:
    it books device time, runs at its watermark, and foreground write p99
    under GC pressure stays at or below the inline-GC-burst baseline."""
    load = _gc_pressure_load(400, 200)
    inline = _gc_pressure_pipe().replay(load)
    p_inline = inline.percentiles(op="W")["p99"]

    pipe = _gc_pressure_pipe()
    pipe.schedule_gc(at=5.0, interval_us=300.0, n_ticks=100)
    actor = pipe.replay(load)
    assert actor.note_counts.get("gc_device_us", 0) > 0  # the actor ran
    assert actor.notes.get("gc_device_us", 0.0) > 0.0    # and booked I/O
    p_actor = actor.percentiles(op="W")["p99"]
    assert p_actor <= p_inline * 1.05  # proactive pacing never worse


def test_paced_gc_cuts_write_p99_below_inline_bursts():
    """Under longer churn the paced GC actor keeps foreground write p99
    strictly below what inline GC bursts cost on the same load."""
    load = _gc_pressure_load(500, 300)
    inline = _gc_pressure_pipe().replay(load)
    pipe = _gc_pressure_pipe()
    pipe.schedule_gc(at=5.0, interval_us=300.0, n_ticks=200)
    paced = pipe.replay(load)
    assert paced.notes.get("gc_device_us", 0.0) > 0.0
    p_inline = inline.percentiles(op="W")["p99"]
    p_paced = paced.percentiles(op="W")["p99"]
    assert p_paced < p_inline, (p_paced, p_inline)


def test_timed_paced_rebuild_routes_reads_and_drains():
    """Paced rebuild: sealed zones pending rebuild serve reads through
    reconstruction (no silent zero reads), and the actor drains the
    pending set while booking device time."""
    from repro.core.handlers import HandlerPipeline
    from repro.sim import TenantSpec, multi_tenant

    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8,
                        chunk_blocks=1, logical_blocks=360,
                        gc_free_segments_low=1)
    zns = ZnsConfig(n_zones=7, zone_cap_blocks=64, block_bytes=BB)
    rng = np.random.default_rng(11)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=11)
    ref = {}
    writes = []
    for i in range(900):
        lba = i % 360
        blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        writes.append((lba, blk))
        ref[lba] = blk[0].copy()
    pipe.precondition(writes)
    arr = pipe.array
    arr.fail_drive(1)
    pipe.schedule_rebuild(1, at=50.0, interval_us=400.0)
    load = multi_tenant([
        TenantSpec(name="reader", kind="uniform", n_ops=300,
                   rate_iops=30_000, read_frac=1.0, seed=31),
    ], logical_blocks=360)
    rec = pipe.replay(load)
    assert not arr._rebuild_pending  # actor drained every pending zone
    assert rec.notes.get("rebuild_device_us", 0.0) > 0.0
    # every read served during the paced rebuild returned correct data
    for (lba, out) in pipe.completed:
        assert np.array_equal(out[0], ref[lba]), lba
    # and the rebuilt drive serves correct data directly
    before = arr.stats.degraded_reads
    got = arr.read(0, 360)
    assert arr.stats.degraded_reads == before
    for lba, want in ref.items():
        assert np.array_equal(got[lba], want)
