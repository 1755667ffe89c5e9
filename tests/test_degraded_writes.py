"""Always-writable degraded array: survivor-width stripes, re-widening
rebuild, crash recovery of mixed-width arrays, and the fault-injection
harness (see DESIGN.md §14).

With a drive failed the array keeps taking writes by opening new stripe
groups at survivor width on the healthy drives; rebuild re-widens those
groups back onto the full drive set.  The tests here pin:

* foreground writes, GC and reads all complete while degraded, across
  raid4/5/6/01;
* after replace + rebuild the array is logically identical to a
  never-failed oracle, and batched/scalar runs under the SAME fault
  schedule leave bit-identical media;
* ``recover_array`` on a crash armed while degraded or during a rebuild
  either recovers (survivor metadata synthesis, zone rewrite) or raises
  :class:`RecoveryError` -- never silently drops durable stripes;
* the :mod:`repro.sim.faults` harness injects failures mid-write, mid-GC
  and mid-checkpoint-save on the timed pipeline, service tier up
  throughout, and the post-rebuild state replays the no-failure oracle;
* an open-loop write load on the timed pipeline acks in full while
  degraded and after the paced rebuild has re-widened every narrow segment;
* the manual-GC escrow floor keeps one restage destination zone.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.array import ZapRaidConfig, ZapRAIDArray
from repro.core.handlers import HandlerPipeline
from repro.core.recovery import RecoveryError, recover_array
from repro.core.zns import DeviceCrashed, ZnsConfig
from repro.sim import FaultEvent, FaultPlan

BB = 256
SCHEMES = [("raid4", 4), ("raid5", 4), ("raid6", 5), ("raid01", 4)]


def _mk(batched, scheme="raid5", n_drives=4, zones=8, logical=360, **kw):
    kw.setdefault("gc_free_segments_low", 2)
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=8,
                        chunk_blocks=1, logical_blocks=logical,
                        batched=batched, **kw)
    zns = ZnsConfig(n_zones=zones, zone_cap_blocks=64, block_bytes=BB)
    return ZapRAIDArray(cfg, zns), cfg, zns


def _write_phase(arr, ref, rng, n, logical, base=0):
    for i in range(n):
        lba = (base + i) % logical
        blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        arr.write(lba, blk)
        ref[lba] = blk[0].copy()


def _check_all(arr, ref):
    for lba, want in ref.items():
        got = arr.read(lba, 1)[0]
        assert np.array_equal(got, want), f"lba {lba} mismatch"


def _assert_media_identical(a1, a0):
    for d1, d0 in zip(a1.drives, a0.drives):
        assert np.array_equal(d1.data, d0.data)
        assert np.array_equal(d1.oob, d0.oob)
        assert np.array_equal(d1.wp, d0.wp)
    assert set(a1.segments) == set(a0.segments)
    for sid in a1.segments:
        assert np.array_equal(a1.segments[sid].valid, a0.segments[sid].valid)
    assert np.array_equal(a1.l2p.flat, a0.l2p.flat)


# ------------------------------------------------- degraded writability


@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_degraded_writes_open_survivor_width_groups(scheme, n_drives):
    """With one drive failed, writes keep landing: new groups open at
    survivor width, reads decode both widths, and GC still runs."""
    arr, cfg, _ = _mk(True, scheme, n_drives)
    rng = np.random.default_rng(11)
    ref = {}
    _write_phase(arr, ref, rng, 300, cfg.logical_blocks)
    arr.flush()
    arr.fail_drive(1)
    assert any(d.failed for d in arr.drives)
    # the array stays writable: fresh data and overwrites of full-width LBAs
    _write_phase(arr, ref, rng, 150, cfg.logical_blocks, base=100)
    arr.flush()
    widths = {len(r.info.drive_ids) for r in arr.segments.values()}
    assert len(widths) > 1, "expected mixed-width segments while degraded"
    assert min(widths) < max(widths) <= cfg.n_drives
    _check_all(arr, ref)
    assert arr.stats.degraded_reads > 0
    # GC also completes while degraded (churn guarantees stale blocks)
    runs_before = arr.stats.gc_runs
    assert arr.gc_once()
    assert arr.stats.gc_runs > runs_before
    _check_all(arr, ref)


@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_rewiden_rebuild_matches_no_failure_oracle(scheme, n_drives):
    """fail -> degraded writes -> replace + rebuild: every LBA reads equal
    to a never-failed oracle run of the same writes, and no survivor-width
    segment survives the re-widening backfill."""
    # degraded mirrors write on a single pair: halve the live set so the
    # survivor pair's zones hold it with GC slack
    logical = 160 if scheme == "raid01" else 360
    n1, n2 = (120, 200) if scheme == "raid01" else (260, 420)

    def run(fail):
        arr, cfg, zns = _mk(True, scheme, n_drives, logical=logical)
        rng = np.random.default_rng(3)
        ref = {}
        _write_phase(arr, ref, rng, n1, cfg.logical_blocks)
        arr.flush()
        if fail:
            arr.fail_drive(2)
        _write_phase(arr, ref, rng, n2, cfg.logical_blocks, base=50)
        arr.flush()
        if fail:
            arr.rebuild_drive(2)
        return arr, cfg, zns, ref

    a_f, cfg, zns, ref_f = run(True)
    a_o, _, _, ref_o = run(False)
    assert ref_f.keys() == ref_o.keys()
    for lba in ref_f:
        assert np.array_equal(ref_f[lba], ref_o[lba])
        assert np.array_equal(a_f.read(lba, 1)[0], a_o.read(lba, 1)[0])
    # re-widening left no narrow groups behind and the drive is healthy
    assert not any(d.failed for d in a_f.drives)
    n_active = len(a_f._active_drive_ids())
    assert all(len(r.info.drive_ids) == n_active
               for r in a_f.segments.values())
    # recovery roundtrip of the mixed-history array is self-consistent
    a_r = recover_array(a_f.drives, cfg, zns)
    _check_all(a_r, ref_f)


@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
def test_batched_vs_scalar_identity_under_fault_schedule(scheme, n_drives):
    """The batched write/GC/rebuild pipelines under the SAME fail/replace
    schedule leave media, OOB, wp and L2P bit-identical to scalar."""
    logical = 160 if scheme == "raid01" else 360
    n1, n2 = (120, 200) if scheme == "raid01" else (240, 400)

    def run(batched):
        arr, cfg, _ = _mk(batched, scheme, n_drives, logical=logical)
        rng = np.random.default_rng(5)
        ref = {}
        _write_phase(arr, ref, rng, n1, cfg.logical_blocks)
        arr.flush()
        arr.fail_drive(0)
        _write_phase(arr, ref, rng, n2, cfg.logical_blocks, base=30)
        arr.flush()
        arr.rebuild_drive(0)
        return arr, ref

    a1, r1 = run(True)
    a0, r0 = run(False)
    _assert_media_identical(a1, a0)
    _check_all(a1, r1)
    _check_all(a0, r0)


# ------------------------------------------------- crash recovery


def test_recover_crash_while_degraded():
    """Crash with a drive failed and survivor-width groups on media: the
    scanner skips the dead drive, synthesizes its OOB from parity, and the
    recovered array serves every LBA (degraded decode), then rebuilds."""
    def run(batched):
        arr, cfg, zns = _mk(batched)
        rng = np.random.default_rng(9)
        ref = {}
        _write_phase(arr, ref, rng, 260, cfg.logical_blocks)
        arr.flush()
        arr.fail_drive(1)
        _write_phase(arr, ref, rng, 300, cfg.logical_blocks, base=40)
        arr.flush()
        # crash: drop the in-memory array, recover from media alone
        a2 = recover_array(arr.drives, cfg, zns)
        return a2, ref, cfg, zns

    a1, ref, cfg, zns = run(True)
    a0, _, _, _ = run(False)
    _check_all(a1, ref)
    _check_all(a0, ref)
    assert a1.stats.degraded_reads > 0
    # still writable at survivor width post-recovery, and rebuildable
    blk = np.random.default_rng(1).integers(0, 256, (1, BB), dtype=np.uint8)
    a1.write(7, blk)
    a1.flush()
    ref[7] = blk[0].copy()
    a1.rebuild_drive(1)
    _check_all(a1, ref)


@pytest.mark.parametrize("batched", [True, False])
def test_recover_crash_armed_during_rebuild(batched):
    """Crash budget bites inside rebuild_drive: some member zones rewritten,
    one mid-zone, the rest untouched (wiped).  recover_array classifies the
    crashed-rebuild zones, rewrites them from survivors, and every LBA
    written before the crash reads back."""
    arr, cfg, zns = _mk(batched)
    rng = np.random.default_rng(13)
    ref = {}
    _write_phase(arr, ref, rng, 260, cfg.logical_blocks)
    arr.flush()
    arr.fail_drive(1)
    _write_phase(arr, ref, rng, 300, cfg.logical_blocks, base=40)
    arr.flush()
    arr.arm_crash(30)  # lands mid-way through the member-zone rewrites
    with pytest.raises(DeviceCrashed):
        arr.rebuild_drive(1)
    a2 = recover_array(arr.drives, cfg, zns)
    _check_all(a2, ref)
    # the finished recovery re-ran the re-widening pass: full width again
    assert not any(d.failed for d in a2.drives)
    a2.rebuild_drive(1)  # idempotent on an already-whole drive
    _check_all(a2, ref)


def test_recover_fails_loudly_on_two_wiped_zones():
    """Two member zones of one segment wiped (no header while others carry
    one) is beyond single-parity reconstruction bookkeeping: the scanner
    must raise RecoveryError, not silently drop the segment."""
    arr, cfg, zns = _mk(True)
    rng = np.random.default_rng(17)
    ref = {}
    _write_phase(arr, ref, rng, 300, cfg.logical_blocks)
    arr.flush()
    sealed = [r for r in arr.segments.values()
              if r.info.seg_id not in arr.open_segments]
    rec = sealed[0]
    for member in (0, 1):
        p = rec.info.drive_ids[member]
        arr.drives[p].reset_zone(rec.info.zone_ids[member])
    with pytest.raises(RecoveryError):
        recover_array(arr.drives, cfg, zns)


def test_recover_fails_loudly_on_wide_wp_spread():
    """A member write pointer more than one group span behind its peers in
    an unsealed segment only happens when a rebuild crashed mid-rewrite --
    the scanner raises instead of dropping the unattributable stripes."""
    arr, cfg, zns = _mk(True)
    rng = np.random.default_rng(19)
    for i in range(40):  # stay short of sealing: one open segment
        arr.write(i, rng.integers(0, 256, (1, BB), dtype=np.uint8))
    arr.flush()
    ost = next(iter(arr.open_segments.values()))
    info = ost.info
    member = 1
    p = info.drive_ids[member]
    z = info.zone_ids[member]
    d = arr.drives[p]
    # simulate the half-rewritten zone: same media, wp rolled back past one
    # group span (media beyond wp is never trusted by the scanner)
    span = info.group_size * info.chunk_blocks
    d.wp[z] = max(info.chunk_blocks, int(d.wp[z]) - (span + 2))
    with pytest.raises(RecoveryError):
        recover_array(arr.drives, cfg, zns)


# ------------------------------------------------- fault injection (timed)


def _timed_pipe(scheme="raid5", seed=0, logical=128, zones=8, **cfg_kw):
    n_drives = 5 if scheme == "raid6" else 4
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=4,
                        chunk_blocks=1, logical_blocks=logical,
                        gc_free_segments_low=1, **cfg_kw)
    zns = ZnsConfig(n_zones=zones, zone_cap_blocks=64, block_bytes=BB)
    return HandlerPipeline.build_timed(cfg, zns, seed=seed,
                                       flush_interval_us=200.0)


def _timed_workload(pipe, *, rounds=3, seed=5):
    """Writes spanning the whole LBA range, paced so scheduled faults land
    mid-stream; returns the per-LBA reference and the end time."""
    logical = pipe.array.cfg.logical_blocks
    rng = np.random.default_rng(seed)
    ref = {}
    t = 0.0
    for _ in range(rounds):
        for lba in range(0, logical - 1, 2):
            blk = rng.integers(0, 256, (2, BB), dtype=np.uint8)
            pipe.submit_write(lba, blk, at=t)
            ref[lba] = blk[0].copy()
            ref[lba + 1] = blk[1].copy()
            t += 8.0
    return ref, t


@pytest.mark.parametrize("scheme", ["raid4", "raid5", "raid6", "raid01"])
def test_fault_injection_replays_no_failure_oracle(scheme):
    """Scripted fail + paced rebuild injected mid-write-stream (GC pressure
    live): after drain, every LBA reads equal to an identical run with no
    faults, and the injector log records what fired."""
    t_fail, t_fix = 700.0, 2600.0
    plan = FaultPlan.scripted([
        FaultEvent(t_us=t_fail, kind="fail", drive=1),
        FaultEvent(t_us=t_fix, kind="rebuild", drive=1, interval_us=25.0),
    ])

    def run(faulted):
        pipe = _timed_pipe(scheme)
        inj = pipe.attach_faults(plan) if faulted else None
        ref, _ = _timed_workload(pipe)
        pipe.drain()
        return pipe, inj, ref

    pf, inj, ref_f = run(True)
    po, _, ref_o = run(False)
    assert [(k, d) for _, k, d in inj.log] == [("fail", 1), ("rebuild", 1)]
    assert inj.log[0][0] == pytest.approx(t_fail)
    # the stream kept committing while degraded, then re-widened
    assert not any(d.failed for d in pf.array.drives)
    assert ref_f.keys() == ref_o.keys()
    for lba in ref_f:
        got_f = pf.array.read(lba, 1)[0]
        got_o = po.array.read(lba, 1)[0]
        assert np.array_equal(got_f, ref_f[lba]), f"faulted lba {lba}"
        assert np.array_equal(got_o, got_f), f"oracle divergence at {lba}"


def test_fault_injection_mid_gc_actor():
    """Failure fired while the background-GC actor is mid-campaign: both
    cleaning and foreground writes complete, reads verify."""
    pipe = _timed_pipe(zones=7, logical=96)
    pipe.schedule_gc(at=400.0, interval_us=150.0, n_ticks=60)
    plan = FaultPlan.scripted([
        FaultEvent(t_us=900.0, kind="fail", drive=2),
        FaultEvent(t_us=3600.0, kind="rebuild", drive=2),
    ])
    inj = pipe.attach_faults(plan)
    ref, _ = _timed_workload(pipe, rounds=8)
    pipe.drain()
    assert len(inj.log) == 2
    assert pipe.array.stats.gc_runs > 0
    for lba, want in ref.items():
        assert np.array_equal(pipe.array.read(lba, 1)[0], want)


def test_probabilistic_fault_plan_round_trips():
    """Seeded MTBF fail/repair cycles: each cycle replaces and re-widens, the
    log matches the plan, and the final array serves the whole LBA range."""
    plan = FaultPlan.probabilistic(
        n_drives=4, horizon_us=2500.0, mtbf_us=900.0,
        repair_after_us=600.0, seed=42, rebuild_interval_us=30.0,
    )
    assert plan.events, "seed must produce at least one fail/repair cycle"
    assert [e.kind for e in plan.events[:2]] == ["fail", "rebuild"]
    pipe = _timed_pipe()
    inj = pipe.attach_faults(plan)
    ref, _ = _timed_workload(pipe, rounds=3, seed=8)
    pipe.drain()
    assert len(inj.log) == len(plan.events)
    assert not any(d.failed for d in pipe.array.drives)
    for lba, want in ref.items():
        assert np.array_equal(pipe.array.read(lba, 1)[0], want)


def test_checkpoint_saves_commit_through_failure_and_rebuild():
    """Async checkpoint saves keep committing while a lane drive is failed
    and during the rebuild; every window restores bit-exact afterwards."""
    from repro.checkpoint.zapraid_ckpt import CheckpointConfig, CheckpointEngine
    from repro.service import BlockDeviceService, QosClass

    cfg = CheckpointConfig(group_size=4, chunk_blocks=1, block_bytes=256,
                           zone_cap_blocks=256, n_zones=16, keep_last=3)
    ckpt, pipe = CheckpointEngine.build_timed(cfg, 1024, seed=0,
                                              flush_interval_us=200.0)
    svc = BlockDeviceService(pipe, max_inflight=16)
    svc.register("ckpt", QosClass("ckpt", priority=2))

    def state(seed):
        rng = np.random.default_rng(seed)
        return {"w": rng.standard_normal(128).astype(np.float32),
                "b": rng.standard_normal(64).astype(np.float32)}

    s0, s1, s2 = state(1), state(2), state(3)
    t0 = ckpt.save_async(0, s0, service=svc)
    svc.drain()
    assert t0.done
    # fail a drive, then save mid-degraded: the stream must keep committing
    plan = FaultPlan.scripted([
        FaultEvent(t_us=pipe.engine.now + 10.0, kind="fail", drive=1),
    ])
    inj = pipe.attach_faults(plan)
    t1 = ckpt.save_async(1, s1, service=svc)
    svc.drain()
    assert t1.done and inj.log and inj.log[0][1] == "fail"
    assert any(d.failed for d in pipe.array.drives)
    # paced rebuild with another save racing it
    plan2 = FaultPlan.scripted([
        FaultEvent(t_us=pipe.engine.now + 20.0, kind="rebuild", drive=1,
                   interval_us=25.0),
    ])
    pipe.attach_faults(plan2)
    t2 = ckpt.save_async(2, s2, service=svc)
    svc.drain()
    assert t2.done
    assert not any(d.failed for d in pipe.array.drives)
    for idx, st in ((0, s0), (1, s1), (2, s2)):
        rt = ckpt.restore_async(idx, st, service=svc)
        svc.drain()
        assert rt.done
        for k in st:
            np.testing.assert_array_equal(np.asarray(rt.state[k]), st[k])


# ------------------------------------------------- escrow floor (manual GC)


def _narrow_segments(arr):
    return [sid for sid, rec in arr.segments.items()
            if len(rec.info.drive_ids) < arr.cfg.n_drives]


def test_timed_degraded_write_load_acks_and_rebuild_rewidens():
    """One drive fails before an open-loop overwrite load on the timed
    pipeline.  Every write still acks, on survivor-width stripe groups;
    the paced replace-and-rebuild books device time and re-widens every
    narrow segment; the same load then acks in full at full width."""
    from repro.sim import LatencyRecorder, TenantSpec, multi_tenant

    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8,
                        chunk_blocks=1, logical_blocks=256,
                        gc_free_segments_low=1)
    zns = ZnsConfig(n_zones=16, zone_cap_blocks=64, block_bytes=BB)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=7,
                                       flush_interval_us=200.0)
    rng = np.random.default_rng(7)
    pipe.precondition(
        (lba, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        for lba in range(256)
    )
    load = multi_tenant([
        TenantSpec(name="writer", kind="uniform", n_ops=300,
                   rate_iops=50_000, read_frac=0.0, seed=71),
    ], logical_blocks=256)

    def shifted():  # arrivals re-based onto the engine's current clock
        return [dataclasses.replace(r, t_us=r.t_us + pipe.engine.now)
                for r in load]

    pipe.array.fail_drive(1)
    degraded = pipe.replay(shifted())
    assert degraded.percentiles(op="W")["n"] == len(load)
    assert _narrow_segments(pipe.array)

    pipe.schedule_rebuild(1, at=pipe.engine.now + 10.0, interval_us=20.0)
    pipe.drain()
    assert degraded.notes.get("rebuild_device_us", 0.0) > 0.0
    assert not pipe.array.drives[1].failed
    assert _narrow_segments(pipe.array) == []

    pipe.recorder = LatencyRecorder()
    rebuilt = pipe.replay(shifted())
    assert rebuilt.percentiles(op="W")["n"] == len(load)
    assert _narrow_segments(pipe.array) == []


def test_manual_gc_keeps_one_restage_destination_zone():
    """gc_free_segments_low == 0 (manual GC) at a handful-of-zones geometry:
    the write path refuses to consume the last free zone, so an explicit
    gc_once() always has a restage destination and un-wedges the array."""
    arr, cfg, zns = _mk(True, zones=5, logical=200, gc_free_segments_low=0)
    assert arr.reserved_zones() == 1
    # the manual-GC floor gates zone opens only; it never shifts the
    # free-segment arithmetic the GC watermarks see
    assert arr.free_segment_count() == arr._min_free_zones()
    rng = np.random.default_rng(23)
    ref = {}
    wedge_lba = None
    for i in range(2000):
        lba = i % 120  # churn a narrow range: victims stay reclaimable
        blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        try:
            arr.write(lba, blk)
        except RuntimeError as e:
            assert "GC required" in str(e)
            wedge_lba = lba  # its block may be staged; value is ambiguous
            break
        ref[lba] = blk[0].copy()
    assert wedge_lba is not None, "workload must hit the reserved-zone floor"
    # manual GC succeeds because the escrow zone is still free
    assert arr.gc_once()
    blk0 = rng.integers(0, 256, (1, BB), dtype=np.uint8)
    arr.write(0, blk0)
    ref[0] = blk0[0].copy()
    arr.flush()
    for lba, want in ref.items():
        if lba == wedge_lba:
            continue
        assert np.array_equal(arr.read(lba, 1)[0], want)


def test_manual_gc_floor_skipped_on_tiny_geometry():
    """Below header+footer headroom the floor would make the array unusable
    from block zero: it stays off and reserved_zones() reports 0."""
    arr, _, _ = _mk(True, zones=2, logical=40, gc_free_segments_low=0)
    assert arr.reserved_zones() == 0


# ------------------------------------------------- observability hooks


def test_degraded_mode_gauge_and_narrow_commit_span():
    """Observe-only PR-9 hooks: the degraded_mode gauge tracks drive health
    and survivor-width commits emit stripe.commit_narrow spans."""
    from repro.obs import MetricsRegistry, standard_collector

    pipe = _timed_pipe()
    tracer = pipe.attach_obs()
    reg = MetricsRegistry()
    collect = standard_collector(pipe)
    collect(reg)
    assert reg.gauges["array/degraded_mode"] == 0.0
    plan = FaultPlan.scripted([FaultEvent(t_us=600.0, kind="fail", drive=1)])
    pipe.attach_faults(plan)
    ref, _ = _timed_workload(pipe, rounds=2)
    pipe.drain()
    collect(reg)
    assert reg.gauges["array/degraded_mode"] == 1.0
    names = {e["name"] for e in tracer.events}
    assert "stripe.commit_narrow" in names
