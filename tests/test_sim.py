"""Discrete-event timed I/O engine: engine, timed devices, workloads, QoS.

Covers the PR-3 subsystem end to end:

* event-heap ordering and determinism;
* TimedDrive queueing discipline (one Zone Write in flight per zone,
  qd<=4 Zone Appends per zone, channel contention);
* ZNS satellite fixes (max_open_zones enforcement, replace() preserving
  lifetime counters);
* workload generation (MSR trace parsing, synthetic determinism);
* the timed pipeline (write/read roundtrip, latency recording);
* timing-driven Zone-Append disorder: same logical state as the RNG
  permutation path across RAID schemes, including after crash recovery;
* degraded reads under load showing tail inflation.
"""
from collections import deque

import numpy as np
import pytest

from repro.core.array import ZapRaidConfig, ZapRAIDArray
from repro.core.handlers import HandlerPipeline
from repro.core.recovery import recover_array
from repro.core.zns import (
    OOB_DTYPE,
    CrashBudget,
    SimZnsDrive,
    TooManyOpenZones,
    ZnsConfig,
    ZoneState,
)
from repro.obs import Tracer
from repro.sim import (
    Engine,
    Request,
    ServiceModel,
    TenantSpec,
    TimedDrive,
    multi_tenant,
    parse_msr_trace,
    synthetic,
)


# ------------------------------------------------------------------- engine


def test_engine_orders_events_and_is_deterministic():
    eng = Engine()
    fired = []
    eng.at(5.0, fired.append, "c")
    eng.at(1.0, fired.append, "a")
    eng.at(1.0, fired.append, "b")  # same instant: scheduling order wins
    eng.after(0.5, fired.append, "first")
    assert eng.run() == 4
    assert fired == ["first", "a", "b", "c"]
    assert eng.now == 5.0
    eng.at(2.0, fired.append, "late")  # in the past: clamped to now
    eng.run()
    assert eng.now == 5.0 and fired[-1] == "late"


def test_engine_run_until():
    eng = Engine()
    out = []
    for t in (1.0, 2.0, 3.0):
        eng.at(t, out.append, t)
    assert eng.run(until=2.0) == 2
    assert out == [1.0, 2.0] and eng.pending() == 1


# -------------------------------------------------------------- timed drives


def _drive(seed=0, **svc):
    eng = Engine()
    cfg = ZnsConfig(n_zones=4, zone_cap_blocks=64, block_bytes=512)
    service = ServiceModel(block_bytes=512, **svc)
    return eng, TimedDrive(cfg, 0, engine=eng, service=service, seed=seed)


def test_zone_write_serializes_per_zone():
    eng, d = _drive(n_channels=8)
    t1 = d.book_zone_write(0, 1, 0.0)
    t2 = d.book_zone_write(0, 1, 0.0)   # same zone: must wait for t1
    t3 = d.book_zone_write(1, 1, 0.0)   # other zone: starts immediately
    assert t2 > t1
    assert t3 < t2  # inter-zone parallelism


def test_zone_append_qd_limit():
    eng, d = _drive(n_channels=16, jitter_sigma=0.0)
    done = [d.book_append(0, 1, 0.0) for _ in range(8)]
    # first 4 run concurrently; the 5th cannot start before one of them ends
    assert done[4] > min(done[:4])
    # in-flight never exceeds the qd: the completion times of 8 serial-ish
    # bookings must span at least two "waves" of service
    svc1 = d.service.zone_append_us(1, 1)
    assert max(done) > 1.5 * svc1


def test_channels_shared_between_reads_and_writes():
    eng, d = _drive(n_channels=1, jitter_sigma=0.0)
    t_w = d.book_zone_write(0, 1, 0.0)
    t_r = d.book_read(1, 0.0)
    assert t_r > t_w  # the single channel serializes the read behind the write


def test_timed_drive_media_matches_functional():
    eng, d = _drive()
    from repro.core.zns import OOB_DTYPE
    blocks = np.full((2, 512), 7, np.uint8)
    oobs = np.zeros(2, dtype=OOB_DTYPE)
    d.zone_write(0, 0, blocks, oobs)
    assert int(d.wp[0]) == 2
    off = d.zone_append_commit(0, blocks, oobs)
    assert off == 2 and int(d.wp[0]) == 4
    assert d.chunk_completion(0, 0) is not None
    assert d.chunk_completion(0, 2) is not None
    np.testing.assert_array_equal(d.read(0, 0, 2), blocks)


# -------------------------------------------------- ZNS satellites (PR 3)


def test_max_open_zones_enforced():
    cfg = ZnsConfig(n_zones=8, zone_cap_blocks=16, block_bytes=64, max_open_zones=2)
    d = SimZnsDrive(cfg, 0)
    from repro.core.zns import OOB_DTYPE
    blk = np.zeros((1, 64), np.uint8)
    oob = np.zeros(1, dtype=OOB_DTYPE)
    d.zone_write(0, 0, blk, oob)
    d.zone_append_begin(1)
    assert d.open_zone_count() == 2
    with pytest.raises(TooManyOpenZones):
        d.zone_write(2, 0, blk, oob)
    with pytest.raises(TooManyOpenZones):
        d.zone_append_begin(3)
    with pytest.raises(TooManyOpenZones):
        d.zone_append_commit(3, blk, oob)
    # sealing one frees a slot
    d.finish_zone(0)
    d.zone_write(2, 0, blk, oob)
    assert d.open_zone_count() == 2
    # writing into an already-open zone never trips the limit
    d.zone_write(2, 1, blk, oob)


def test_replace_preserves_lifetime_counters():
    cfg = ZnsConfig(n_zones=4, zone_cap_blocks=16, block_bytes=64)
    d = SimZnsDrive(cfg, 0)
    from repro.core.zns import OOB_DTYPE
    blk = np.full((1, 64), 3, np.uint8)
    oob = np.zeros(1, dtype=OOB_DTYPE)
    for _ in range(5):
        d.zone_write(0, int(d.wp[0]), blk, oob)
    d.reset_zone(0)
    assert (d.blocks_written, d.zone_resets) == (5, 1)
    d.fail()
    d.replace()
    assert not d.failed
    assert (d.blocks_written, d.zone_resets) == (5, 1)  # counters survive swap
    assert int(d.wp[0]) == 0 and d.state[0] == ZoneState.EMPTY
    assert not d.data.any()
    # budget identity is preserved too
    assert isinstance(d.budget, CrashBudget)


def test_replace_write_amp_accounting_spans_rebuild():
    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=4,
                        chunk_blocks=1, logical_blocks=64, gc_free_segments_low=1)
    zns = ZnsConfig(n_zones=8, zone_cap_blocks=32, block_bytes=128)
    arr = ZapRAIDArray(cfg, zns)
    rng = np.random.default_rng(0)
    for lba in range(48):
        arr.write(lba, rng.integers(0, 256, (1, 128), dtype=np.uint8))
    arr.flush()
    before = arr.drives[2].blocks_written
    assert before > 0
    arr.fail_drive(2)
    arr.rebuild_drive(2)
    # the rebuilt drive's counter kept its history and grew with the rebuild
    assert arr.drives[2].blocks_written > before


# ------------------------------------------------------------------ workload


def test_parse_msr_trace():
    text = "\n".join([
        "Timestamp,Hostname,DiskNumber,Type,Offset,Size,ResponseTime",
        "128166372003061629,src1,0,Write,8192,4096,100",
        "128166372003061529,src1,0,Read,0,1024,90",      # earlier ts
        "128166372003071629,src1,0,Write,1048576,8192,110",
        "garbage line",
    ])
    reqs = parse_msr_trace(text, block_bytes=4096, logical_blocks=64)
    assert len(reqs) == 3
    assert [r.op for r in reqs] == ["R", "W", "W"]  # sorted by time
    assert reqs[0].t_us == 0.0
    assert reqs[1].t_us == pytest.approx(10.0)      # 100 ticks = 10 us
    assert reqs[1].lba == 2 and reqs[1].n_blocks == 1
    assert reqs[2].n_blocks == 2
    assert all(r.lba + r.n_blocks <= 64 for r in reqs)


def test_synthetic_workloads_deterministic_and_bounded():
    for kind in ("seq", "uniform", "hotspot", "zipf"):
        spec = TenantSpec(name="t", kind=kind, n_ops=200, rate_iops=10_000,
                          read_frac=0.3, seed=5)
        a = synthetic(spec, logical_blocks=128)
        b = synthetic(spec, logical_blocks=128)
        assert a == b
        assert all(0 <= r.lba < 128 for r in a)
        assert all(a[i].t_us <= a[i + 1].t_us for i in range(len(a) - 1))


def test_bursty_arrivals_cluster():
    calm = synthetic(TenantSpec(name="c", n_ops=400, rate_iops=10_000, seed=1),
                     logical_blocks=64)
    burst = synthetic(TenantSpec(name="b", n_ops=400, rate_iops=10_000,
                                 burst_factor=4.0, seed=1), logical_blocks=64)
    def cv2(rs):  # squared coefficient of variation of inter-arrival gaps
        g = np.diff([r.t_us for r in rs])
        return np.var(g) / np.mean(g) ** 2

    # Poisson gaps have CV^2 ~ 1; on-off modulation pushes it well above
    assert cv2(calm) < 1.5 < cv2(burst)


def test_multi_tenant_merge():
    reqs = multi_tenant([
        TenantSpec(name="a", n_ops=50, rate_iops=5_000, seed=1),
        TenantSpec(name="b", n_ops=50, rate_iops=5_000, read_frac=1.0, seed=2),
    ], logical_blocks=64)
    assert len(reqs) == 100
    assert {r.tenant for r in reqs} == {"a", "b"}
    assert all(reqs[i].t_us <= reqs[i + 1].t_us for i in range(len(reqs) - 1))


# ------------------------------------------------------------ timed pipeline


def _timed_pipe(scheme="raid5", group_size=4, seed=0, **cfg_kw):
    cfg = ZapRaidConfig(scheme=scheme, n_drives=4, group_size=group_size,
                        chunk_blocks=1, logical_blocks=128,
                        gc_free_segments_low=1, **cfg_kw)
    zns = ZnsConfig(n_zones=8, zone_cap_blocks=64, block_bytes=256)
    return HandlerPipeline.build_timed(cfg, zns, seed=seed)


def test_timed_write_read_roundtrip_records_latency():
    pipe = _timed_pipe()
    rng = np.random.default_rng(0)
    ref = {}
    t = 0.0
    for lba in range(24):
        blk = rng.integers(0, 256, (1, 256), dtype=np.uint8)
        ref[lba] = blk[0].copy()
        t += 20.0
        pipe.submit_write(lba, blk, at=t)
    pipe.drain()
    got = {}
    for lba in range(24):
        pipe.submit_read(lba, 1, cb=lambda out, l=lba: got.__setitem__(l, out[0]),
                         at=t + 100.0 + lba)
    pipe.drain()
    assert all(np.array_equal(got[l], v) for l, v in ref.items())
    rec = pipe.recorder
    w = rec.percentiles(op="W")
    r = rec.percentiles(op="R")
    assert w["n"] == 24 and r["n"] == 24
    assert w["p99"] >= w["p50"] > 0
    assert r["p50"] > 50.0  # a NAND read costs real virtual time
    assert pipe.counters["dispatch"] == 48
    assert pipe.counters["encoding"] >= 8   # stripes committed
    assert pipe.counters["completion"] == 48


def test_timed_acks_follow_virtual_time():
    pipe = _timed_pipe()
    acks = []
    blk = np.ones((1, 256), np.uint8)
    for i in range(12):  # 4 full stripes (k=3) -> immediate group commits
        pipe.submit_write(i, blk, cb=acks.append, at=float(i))
    pipe.drain()
    assert len(acks) == 12
    assert all(a >= 0 for a in acks)
    # engine clock advanced beyond the last submission: device time is real
    assert pipe.engine.now > 11.0


def test_group_barrier_waits_under_backpressure():
    pipe = _timed_pipe(group_size=8)
    blk = np.ones((1, 256), np.uint8)
    # blast arrivals at t=0: consecutive groups must wait for one another
    for i in range(96):
        pipe.submit_write(i % 128, blk, at=0.0)
    pipe.drain()
    assert pipe.recorder.notes.get("group_barrier_wait_us", 0.0) > 0.0


def test_flush_tick_pads_stalled_stripes():
    pipe = _timed_pipe()
    blk = np.ones((1, 256), np.uint8)
    reqs = [Request(0.0, "w", "W", 5, 1)]  # a lone write: stripe never fills
    rec = pipe.replay(reqs, payload_fn=lambda r: blk)
    assert rec.percentiles(op="W")["n"] == 1
    # the ack came from the timeout-flush path, not from a stripe fill
    assert pipe.array.stats.padded_blocks > 0


# ------------------------------------- timing-driven Zone-Append disorder


def _write_workload(rng, n_ops, logical):
    ops = []
    for _ in range(n_ops):
        n = int(rng.integers(1, 3))
        lba = int(rng.integers(0, logical - n))
        ops.append((lba, rng.integers(0, 256, (n, 256), dtype=np.uint8)))
    return ops


@pytest.mark.parametrize("scheme", ["raid5", "raid4", "raid6", "raid01"])
def test_timed_disorder_consistent_with_rng_path(scheme):
    """Timing-driven completion order must yield the same *logical* state as
    the RNG-permutation fallback: identical read-back before and after crash
    recovery, even though physical placements (CST contents) differ."""
    rng = np.random.default_rng(42)
    ops = _write_workload(rng, 60, 128)
    ref = {}
    for lba, data in ops:
        for i in range(data.shape[0]):
            ref[lba + i] = data[i].copy()

    # timed path: disorder from device timing
    pipe = _timed_pipe(scheme=scheme, seed=9)
    t = 0.0
    for lba, data in ops:
        t += 15.0
        pipe.submit_write(lba, data, at=t)
    pipe.drain()
    timed_arr = pipe.array

    # RNG path: seeded permutation in the functional array
    cfg = ZapRaidConfig(scheme=scheme, n_drives=4, group_size=4,
                        chunk_blocks=1, logical_blocks=128,
                        gc_free_segments_low=1, append_order="rng")
    zns = ZnsConfig(n_zones=8, zone_cap_blocks=64, block_bytes=256)
    rng_arr = ZapRAIDArray(cfg, zns)
    for lba, data in ops:
        rng_arr.write(lba, data)
    rng_arr.flush()

    for arr in (timed_arr, rng_arr):
        for lba, want in ref.items():
            np.testing.assert_array_equal(arr.read(lba, 1)[0], want)

    # crash-recover both from their media: recovered state is bit-identical
    # to the reference (and hence across the two ordering paths)
    for arr in (timed_arr, rng_arr):
        rec = recover_array(arr.drives, arr.cfg, arr.zns_cfg)
        for lba, want in ref.items():
            np.testing.assert_array_equal(rec.read(lba, 1)[0], want)


def test_timed_disorder_degraded_reads():
    """CST built under timing-driven placement still decodes every chunk."""
    pipe = _timed_pipe(scheme="raid5", seed=4)
    rng = np.random.default_rng(3)
    ref = {}
    t = 0.0
    for lba in range(96):
        blk = rng.integers(0, 256, (1, 256), dtype=np.uint8)
        ref[lba] = blk[0].copy()
        t += 10.0
        pipe.submit_write(lba, blk, at=t)
    pipe.drain()
    pipe.array.fail_drive(2)
    for lba, want in ref.items():
        np.testing.assert_array_equal(pipe.array.read(lba, 1)[0], want)
    assert pipe.array.stats.degraded_reads > 0


# ------------------------------------------------------------- QoS scenarios


def test_degraded_read_under_load_inflates_tail():
    def run(fail):
        pipe = _timed_pipe(seed=13)
        rng = np.random.default_rng(1)
        pipe.precondition(
            (lba, rng.integers(0, 256, (1, 256), dtype=np.uint8))
            for lba in range(128)
        )
        if fail:
            pipe.array.fail_drive(1)
        load = synthetic(
            TenantSpec(name="r", kind="uniform", n_ops=300,
                       rate_iops=60_000, read_frac=1.0, seed=8),
            logical_blocks=128,
        )
        return pipe.replay(load).percentiles(op="R")

    healthy, degraded = run(False), run(True)
    assert degraded["p99"] > healthy["p99"]
    assert degraded["p50"] >= healthy["p50"]


def test_rebuild_under_load_books_device_time():
    pipe = _timed_pipe(seed=17)
    rng = np.random.default_rng(2)
    pipe.precondition(
        (lba, rng.integers(0, 256, (1, 256), dtype=np.uint8))
        for lba in range(128)
    )
    pipe.array.fail_drive(1)
    pipe.schedule_rebuild(1, at=30.0)
    load = synthetic(
        TenantSpec(name="r", kind="uniform", n_ops=120,
                   rate_iops=30_000, read_frac=1.0, seed=9),
        logical_blocks=128,
    )
    rec = pipe.replay(load)
    assert rec.notes.get("rebuild_device_us", 0.0) > 0.0
    assert not pipe.array.drives[1].failed
    # post-rebuild the array reads clean without degraded decodes
    got = pipe.array.read(0, 1)
    assert got.shape == (1, 256)


# ------------------------- batched booking: bit-identity with per command
#
# The reference below is the per-command booking the drive model used before
# it booked a group's commands per drive in one pass: one scalar jitter draw,
# two ``np.argmin`` channel picks and one service-model call per command.
# The batched booking must reproduce its virtual times bit for bit.


def _ref_jitter(d):
    return float(np.exp(d.jitter_rng.normal(0.0, d.service.jitter_sigma)))


def _ref_grab_channel(d, floor):
    i = int(np.argmin(d.channels))
    return max(floor, d.channels[i])


def _ref_book_channel(d, t_done):
    i = int(np.argmin(d.channels))
    d.channels[i] = t_done


def _ref_book_zone_write(d, zone, n_blocks, floor):
    start = _ref_grab_channel(d, max(floor, float(d.t_zone_free[zone])))
    svc = d.service.zone_write_us(n_blocks) * _ref_jitter(d)
    done = start + svc
    d.t_zone_free[zone] = done
    _ref_book_channel(d, done)
    d.busy_us += svc
    d.engine.touch_io(done)
    if d.tracer is not None:
        d.tracer.span(d._trace_track, "zone_write", start, done,
                      zone=zone, n_blocks=n_blocks)
    return done


def _ref_book_append(d, zone, n_blocks, floor):
    slots = d.za_slots.setdefault(zone, [])
    start = _ref_grab_channel(d, floor)
    busy = sorted(s for s in slots if s > start)
    if len(busy) >= d.service.append_qd:
        start = busy[len(busy) - d.service.append_qd]
        busy = [s for s in busy if s > start]
    qd_now = len(busy) + 1
    svc = d.service.zone_append_us(n_blocks, qd_now) * _ref_jitter(d)
    done = start + svc
    busy.append(done)
    d.za_slots[zone] = busy[-d.service.append_qd:]
    _ref_book_channel(d, done)
    d.busy_us += svc
    d.engine.touch_io(done)
    if d.tracer is not None:
        d.tracer.span(d._trace_track, "zone_append", start, done,
                      zone=zone, n_blocks=n_blocks, qd=qd_now)
    return done


def _ref_book_read(d, n_blocks, floor):
    max_b = max(1, d.service.read_cmd_max_blocks)
    done = floor
    remaining = n_blocks
    while remaining > 0:
        nb = min(remaining, max_b)
        start = _ref_grab_channel(d, floor)
        svc = d.service.read_us(nb) * _ref_jitter(d)
        t = start + svc
        _ref_book_channel(d, t)
        d.busy_us += svc
        done = max(done, t)
        remaining -= nb
        if d.tracer is not None:
            d.tracer.span(d._trace_track, "read", start, t, n_blocks=nb)
    d.engine.touch_io(done)
    return done


def _ref_plan_group_appends(drives, zone_ids, ops, chunk_blocks, floor):
    for d in {d for _, d in ops}:
        drives[d].clear_planned()
    done = []
    for idx, (_, d) in enumerate(ops):
        t = _ref_book_append(drives[d], zone_ids[d], chunk_blocks, floor)
        done.append((t, idx))
    done.sort()
    for t, idx in done:
        _, d = ops[idx]
        drives[d]._planned.setdefault(zone_ids[d], deque()).append(t)
    return [idx for _, idx in done], done[-1][0]


def _ref_zone_append_commit(d, zone, blocks, oobs, crcs=None):
    off = SimZnsDrive.zone_append_commit(d, zone, blocks, oobs, crcs)
    planned = d._planned.get(zone)
    if planned:
        done = planned.popleft()
        d.engine.touch_io(done)
    else:
        done = _ref_book_append(d, zone, blocks.shape[0], d.engine.now)
    d.chunk_done[(zone, off)] = done
    return off


def _ref_zone_append_commit_many(d, zone, chunks, oobs, crcs=None):
    offs = SimZnsDrive.zone_append_commit_many(d, zone, chunks, oobs, crcs)
    planned = d._planned.get(zone)
    for off in offs:
        if planned:
            done = planned.popleft()
            d.engine.touch_io(done)
        else:
            done = _ref_book_append(d, zone, chunks.shape[1], d.engine.now)
        d.chunk_done[(zone, int(off))] = done
    return offs


def _use_reference_booking(monkeypatch):
    """Route every booking of the drive model through the reference."""
    import repro.sim.device as device
    monkeypatch.setattr(device, "plan_group_appends", _ref_plan_group_appends)
    for name, fn in (("book_zone_write", _ref_book_zone_write),
                     ("book_append", _ref_book_append),
                     ("book_read", _ref_book_read),
                     ("zone_append_commit", _ref_zone_append_commit),
                     ("zone_append_commit_many", _ref_zone_append_commit_many)):
        monkeypatch.setattr(TimedDrive, name, fn)


def _drive_set(seed, sigma, traced, n=4):
    eng = Engine()
    cfg = ZnsConfig(n_zones=4, zone_cap_blocks=4096, block_bytes=16)
    kw = {} if sigma is None else {"jitter_sigma": sigma}
    service = ServiceModel(block_bytes=4096, **kw)
    drives = [TimedDrive(cfg, i, engine=eng, service=service, seed=seed + 101 * i)
              for i in range(n)]
    tracer = Tracer(eng) if traced else None
    for d in drives:
        d.tracer = tracer
    return eng, drives, tracer


def _drive_state(d):
    return (list(d.channels), {z: list(s) for z, s in d.za_slots.items()},
            {z: list(q) for z, q in d._planned.items()}, d.busy_us,
            d.t_zone_free.tolist(), d.jitter_rng.bit_generator.state)


@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
@pytest.mark.parametrize("sigma", [0.0, None], ids=["sigma0", "sigma_default"])
@pytest.mark.parametrize("chunk_blocks", [1, 4])
@pytest.mark.parametrize("members", [(0, 1, 2, 3), (0, 2, 3)],
                         ids=["width4", "narrow3"])
@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_batched_booking_bit_identical_to_per_command(
        seed, members, chunk_blocks, sigma, traced):
    """``plan_group_appends`` (one pass per drive), ``book_read`` (one jitter
    draw per call) and ``book_zone_write`` give the per-command reference's
    issue order, group time, planned queues, channels, qd slots, busy time,
    RNG state, I/O watermark and tracer spans, over consecutive groups with
    floors before and after earlier completions."""
    from repro.sim.device import plan_group_appends
    ref_eng, ref_drives, ref_tr = _drive_set(seed, sigma, traced)
    new_eng, new_drives, new_tr = _drive_set(seed, sigma, traced)
    rng = np.random.default_rng(seed)
    n = len(members)
    zone_ids = tuple(int(z) for z in rng.integers(0, 4, n))
    group_done = 0.0
    for g in range(4):
        s_count = int(rng.integers(1, 40))
        ops = [(s, d) for s in range(s_count) for d in range(n)]
        if g == 3:   # any submission order, not only stripe by stripe
            ops = [ops[i] for i in rng.permutation(len(ops))]
        # a floor before the last group's completion, then after it
        floor = group_done * (0.5 if g % 2 else 1.0) + 3.0 * g
        ref = _ref_plan_group_appends([ref_drives[m] for m in members],
                                      zone_ids, ops, chunk_blocks, floor)
        new = plan_group_appends([new_drives[m] for m in members],
                                 zone_ids, ops, chunk_blocks, floor)
        assert new == ref
        group_done = ref[1]
        for m in members:
            nb = int(rng.integers(1, 40))
            assert (new_drives[m].book_read(nb, floor + 1.0)
                    == _ref_book_read(ref_drives[m], nb, floor + 1.0))
        z = int(rng.integers(0, 4))
        assert (new_drives[members[0]].book_zone_write(z, chunk_blocks, floor)
                == _ref_book_zone_write(ref_drives[members[0]], z,
                                        chunk_blocks, floor))
        for d_ref, d_new in zip(ref_drives, new_drives):
            assert _drive_state(d_new) == _drive_state(d_ref)
        assert new_eng.io_watermark == ref_eng.io_watermark
    # unplanned appends fall back to booking at the engine's clock
    chunks = np.zeros((7, chunk_blocks, 16), np.uint8)
    oobs = np.zeros((7, chunk_blocks), OOB_DTYPE)
    for eng in (ref_eng, new_eng):
        eng.now = group_done
    for d_ref, d_new in zip(ref_drives, new_drives):
        d_ref.clear_planned()
        d_new.clear_planned()
        assert ([d_new.book_append(1, chunk_blocks, group_done) for _ in range(5)]
                == [_ref_book_append(d_ref, 1, chunk_blocks, group_done)
                    for _ in range(5)])
        assert (d_new.zone_append_commit(2, chunks[0], oobs[0])
                == _ref_zone_append_commit(d_ref, 2, chunks[0], oobs[0]))
        d_new.zone_append_commit_many(2, chunks, oobs)
        _ref_zone_append_commit_many(d_ref, 2, chunks, oobs)
        assert d_new.chunk_done == d_ref.chunk_done
        assert _drive_state(d_new) == _drive_state(d_ref)
    assert new_eng.io_watermark == ref_eng.io_watermark
    if traced:   # in the order emitted, and as exported
        assert new_tr.events == ref_tr.events
        assert new_tr.to_trace_events() == ref_tr.to_trace_events()


@pytest.mark.parametrize("batched", [True, False], ids=["batched", "legacy"])
def test_timed_pipeline_books_as_per_command_reference(monkeypatch, batched):
    """A traced timed pipeline (group commits through the bulk and the
    per-command committer, reads, a drive failure and a rebuild) gives the
    same virtual clock, completion times, busy time and exported trace
    events as with every booking routed through the per-command
    reference."""
    def run():
        pipe = _timed_pipe(seed=5, batched=batched)
        tracer = pipe.attach_obs()
        rng = np.random.default_rng(11)
        t = 0.0
        for lba, data in _write_workload(rng, 80, 128):
            t += 6.0
            pipe.submit_write(lba, data, at=t)
        for i in range(40):
            pipe.submit_read((i * 7) % 120, 3, at=t + 10.0 * i)
        pipe.schedule_drive_failure(1, t + 450.0)
        pipe.schedule_rebuild(1, t + 600.0, interval_us=40.0)
        pipe.drain()
        return (pipe.engine.now, pipe.engine.io_watermark,
                [(d.busy_us, dict(d.chunk_done)) for d in pipe.array.drives],
                tracer.events, tracer.to_trace_events())

    new = run()
    with monkeypatch.context() as mp:
        _use_reference_booking(mp)
        ref = run()
    assert new == ref
