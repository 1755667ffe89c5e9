"""End-to-end data integrity (DESIGN.md §15): per-block checksums,
silent-corruption fault injection, verify-on-read self-repair, the paced
scrub actor, and checksum-validated recovery.

The acceptance scenario from the PR: a scripted fault plan corrupting
over 1% of written blocks (mixed kinds, across raid4/5/6/01, including a
run with a concurrently failed drive) must end with every corruption
detected, the media bit-identical to a no-fault oracle after a scrub
pass, zero wrong bytes ever returned to a reader, and an unrepairable
double fault surfacing :class:`IntegrityError` instead of garbage.
"""
import json

import jax
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.array import IntegrityError, ZapRaidConfig, ZapRAIDArray
from repro.core.handlers import HandlerPipeline
from repro.core.recovery import recover_array
from repro.core.segment import (
    FooterError,
    footer_entries_per_block,
    footer_has_crc,
    pack_footer,
    unpack_footer,
)
from repro.core.zns import OOB_DTYPE, ZnsConfig
from repro.integrity import CRC_BYTES, crc32c, crc32c_many, crc32c_pack, verify_many
from repro.integrity import checksum
from repro.integrity.checksum import R_MIN, R_TILE, crc32c_device
from repro.obs import HostSpans
from repro.sim.faults import MEDIA_KINDS, FaultEvent, FaultPlan

BB = 256
SCHEMES = [("raid4", 4), ("raid5", 4), ("raid6", 6), ("raid01", 4)]


# --------------------------------------------------------------- checksum unit


def test_crc32c_known_vectors():
    # RFC 3720 / iSCSI check value for b"123456789"
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0
    assert crc32c(bytes(32)) == 0x8A9136AA  # 32 zero bytes


def test_crc32c_many_matches_scalar():
    rng = np.random.default_rng(3)
    blocks = rng.integers(0, 256, (17, BB), dtype=np.uint8)
    many = crc32c_many(blocks)
    for i in range(blocks.shape[0]):
        assert int(many[i]) == crc32c(blocks[i].tobytes())
    packed = crc32c_pack(many)
    assert packed.shape == (17, CRC_BYTES)
    assert (packed.view("<u4").reshape(-1) == many).all()
    ok = verify_many(blocks, many)
    assert ok.all()
    blocks[5, 0] ^= 1
    assert not verify_many(blocks, many)[5]


# --------------------------------------------------- device CRC32C (GF(2) product)

BUCKETS = [R_MIN << i for i in range((R_TILE // R_MIN).bit_length())]
DEVICE_ROWS = sorted({1, R_MIN - 1, *BUCKETS, R_TILE + 1, 2 * R_TILE + 17})


def _rows(fill, n, length):
    if fill == "random":
        return np.random.default_rng(n * 7 + length).integers(
            0, 256, (n, length), dtype=np.uint8)
    return np.full((n, length), 0 if fill == "zeros" else 0xFF, np.uint8)


@pytest.mark.parametrize("fill", ("random", "zeros", "ones"))
@pytest.mark.parametrize("length", (4096, BB))
@pytest.mark.parametrize("n", DEVICE_ROWS)
def test_crc32c_device_matches_table_walk(fill, length, n):
    """The device path, forced on this backend, gives the table walk's bits
    on uint8 rows and on the int32-packed arena view of the same bytes."""
    blocks = _rows(fill, n, length)
    host = crc32c_many(blocks, device=False)
    assert host.dtype == np.uint32 and host.shape == (n,)
    for i in {0, n - 1}:
        assert int(host[i]) == crc32c(blocks[i].tobytes())
    arena = blocks.view(np.int32)
    for got in (crc32c_many(blocks, device=True), crc32c_many(arena, device=True),
                crc32c_device(blocks), crc32c_device(arena)):
        assert got.dtype == np.uint32
        np.testing.assert_array_equal(got, host)


@pytest.mark.parametrize("length", (4096, BB))
def test_crc32c_device_compiles_nothing_after_its_first_call(length):
    compiles = []

    def listener(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    crc32c_device(_rows("random", 1, length))   # compiles every bucket
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        for n in DEVICE_ROWS:
            crc32c_device(_rows("random", n, length))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == []


@pytest.mark.parametrize("tpu,n,length,path", [
    (False, R_TILE, 4096, "crc32c_host"),
    (True, R_TILE, 4096, "crc32c_device"),
    (True, R_MIN, BB, "crc32c_device"),
    (True, R_MIN - 1, 4096, "crc32c_host"),
    (True, R_MIN, 6, "crc32c_host"),          # not whole int32 words
    (True, R_MIN, 0, "crc32c_host"),
])
def test_default_path_follows_platform_and_shape(monkeypatch, tpu, n, length, path):
    monkeypatch.setattr(checksum, "on_tpu", lambda: tpu)
    blocks = _rows("random", n, length)
    rec = HostSpans(annotate=False).install()
    try:
        got = crc32c_many(blocks)
    finally:
        rec.uninstall()
    assert rec.dispatches == {(path, ((n, length),)): 1}
    np.testing.assert_array_equal(got, crc32c_many(blocks, device=False))


@pytest.mark.parametrize("scheme,n", [("raid5", 4), ("raid6", 6)])
def test_device_path_commits_store_the_host_path_s_media(monkeypatch, scheme, n):
    """A small array whose group commits and rebuild take the device path
    (as on a TPU) stores the same CRC plane and media as the host path."""
    def run(tpu):
        monkeypatch.setattr(checksum, "on_tpu", lambda: tpu)
        cfg = ZapRaidConfig(scheme=scheme, n_drives=n, group_size=16,
                            chunk_blocks=1, logical_blocks=256,
                            gc_free_segments_low=1)
        arr = ZapRAIDArray(cfg, ZnsConfig(n_zones=8, zone_cap_blocks=96,
                                          block_bytes=BB))
        rec = HostSpans(annotate=False).install()
        try:
            _fill(arr, seed=11)
            arr.fail_drive(1)
            arr.rebuild_drive(1)
        finally:
            rec.uninstall()
        return arr, {op for op, _ in rec.dispatches if op.startswith("crc32c")}

    dev, dev_ops = run(True)
    host, host_ops = run(False)
    assert "crc32c_device" in dev_ops and host_ops == {"crc32c_host"}
    for d1, d0 in zip(dev.drives, host.drives):
        np.testing.assert_array_equal(d1.crc, d0.crc)
        np.testing.assert_array_equal(d1.data, d0.data)
        np.testing.assert_array_equal(d1.wp, d0.wp)


# ------------------------------------------------------------ helpers


def _mk(scheme="raid5", n_drives=4, logical=128, zones=12, zone_cap=32,
        **kw):
    kw.setdefault("gc_free_segments_low", 1)
    kw.setdefault("verify_reads", True)
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=4,
                        chunk_blocks=1, logical_blocks=logical, **kw)
    zns = ZnsConfig(n_zones=zones, zone_cap_blocks=zone_cap, block_bytes=BB)
    return ZapRAIDArray(cfg, zns), cfg, zns


def _fill(arr, seed=7):
    rng = np.random.default_rng(seed)
    ref = {}
    for lba in range(arr.cfg.logical_blocks):
        b = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        arr.write(lba, b)
        ref[lba] = b[0].copy()
    arr.flush()
    return ref


def _inject_mixed(arr, rng, frac=0.02, skip_failed=True):
    """Corrupt ~frac of every drive's written blocks with a kind mix,
    keeping every hit *repairable*: at most one data-region fault per
    stripe group (header/footer blocks regenerate independently, so they
    are unconstrained).  Returns the number of blocks hit.  The checksum
    store is never touched, so every hit is detectable."""
    zone_seg = {}  # (phys drive, zone) -> (SegmentInfo, member)
    for rec in arr.segments.values():
        info = rec.info
        for m in range(info.n_drives):
            zone_seg[(info.drive_ids[m], info.zone_ids[m])] = (info, m)
    hit_groups = set()  # (seg_id, group index) with a data fault already
    n_bad = 0
    for di, d in enumerate(arr.drives):
        if skip_failed and d.failed:
            continue
        flat = np.flatnonzero(d.written_mask().reshape(-1))
        n = max(2, int(flat.size * frac))
        take = rng.choice(flat, size=min(n, flat.size), replace=False)
        cap = d.cfg.zone_cap_blocks
        for i, t in enumerate(take):
            z, o = int(t // cap), int(t % cap)
            hit = zone_seg.get((di, z))
            if hit is not None:
                info, _ = hit
                ds = info.data_start()
                de = ds + info.n_stripes * info.chunk_blocks
                if ds <= o < de:
                    span = max(1, info.group_size) * info.chunk_blocks
                    key = (info.seg_id, (o - ds) // span)
                    if key in hit_groups:
                        continue  # second hit in a stripe group: skip
                    hit_groups.add(key)
            kind = i % 3
            if kind == 0:
                d.corrupt_bit_rot(z, o, byte=int(rng.integers(0, BB)),
                                  bit=int(rng.integers(0, 8)))
            elif kind == 1:
                d.mark_unreadable(z, o)
            else:
                src = int(rng.choice(flat))
                d.corrupt_misdirected_write(z, o, src // cap, src % cap)
            n_bad += 1
    return n_bad


def _sealed_zone_set(arr):
    from repro.core.segment import SegmentState
    out = set()
    for rec in arr.segments.values():
        if rec.info.state == int(SegmentState.SEALED):
            for m in range(rec.info.n_drives):
                out.add((rec.info.drive_ids[m], rec.info.zone_ids[m]))
    return out


def _assert_media_oracle(arr, oracle, sealed_only=True):
    sealed = _sealed_zone_set(arr)
    for di, d in enumerate(arr.drives):
        if d.failed:
            continue
        for z in range(d.cfg.n_zones):
            if sealed_only and (di, z) not in sealed:
                continue
            wp = int(d.wp[z])
            assert (d.data[z, :wp] == oracle[di][z, :wp]).all(), \
                f"drive {di} zone {z} differs from oracle"
            assert not d.unc[z, :wp].any(), f"UNC left on d{di} z{z}"


def _repairable_data_victims(arr, member=0, limit=3):
    """Data-region blocks of ``member`` whose chunk is still reconstructible
    from the surviving redundancy if that one block is lost."""
    from repro.core.segment import SegmentState
    out = []
    for rec in sorted(arr.segments.values(), key=lambda r: r.info.seg_id):
        info = rec.info
        if info.state != int(SegmentState.SEALED) or member >= info.n_drives:
            continue
        phys = info.drive_ids[member]
        if arr.drives[phys].failed:
            continue
        scheme = arr._scheme_for(info)
        c = info.chunk_blocks
        for chunk_idx in range(info.n_stripes):
            seq, members = arr._chunk_members(rec, member, chunk_idx)
            if scheme.mirror:
                role = scheme.drive_to_role(member, seq)
                twin = (role + scheme.k) % (2 * scheme.k)
                ok = any(scheme.drive_to_role(d, seq) == twin for d in members)
            else:
                ok = len(members) >= scheme.k
            if ok:
                out.append((phys, info.zone_ids[member],
                            info.data_start() + chunk_idx * c))
                if len(out) >= limit:
                    return out
    return out


# ------------------------------------------- acceptance: scrub vs oracle


@pytest.mark.parametrize("scheme,n", SCHEMES)
def test_scrub_restores_no_fault_oracle(scheme, n):
    """Mixed media faults on >1% of written blocks: one scrub pass detects
    every corruption, repairs in place, and leaves sealed media
    bit-identical to the pre-fault oracle; every read returns the
    reference bytes."""
    arr, _, _ = _mk(scheme, n_drives=n)
    ref = _fill(arr)
    oracle = [d.data.copy() for d in arr.drives]
    rng = np.random.default_rng(11)
    injected = _inject_mixed(arr, rng, frac=0.02)
    assert injected > 0
    assert sum(d.media_faults for d in arr.drives) == injected
    res = arr.scrub_once()
    assert res["repaired"] > 0
    assert arr.stats.integrity_scrub_passes == 1
    _assert_media_oracle(arr, oracle)
    for lba, want in ref.items():
        assert np.array_equal(arr.read(lba, 1)[0], want), f"lba {lba}"


@pytest.mark.parametrize("scheme,n", [("raid6", 6), ("raid01", 4)])
def test_scrub_with_concurrently_failed_drive(scheme, n):
    """Media faults land while a member drive is failed outright: scrub
    skips the dead member, heals the survivors (their redundancy still
    covers single media faults), and after rebuild the whole array reads
    the reference."""
    arr, _, _ = _mk(scheme, n_drives=n)
    ref = _fill(arr)
    arr.fail_drive(1)
    # with a member already out, only corrupt chunks whose remaining
    # redundancy still covers the hit (raid6: k survivors left; raid01:
    # the mirror twin is on a live drive) -- anything more is the
    # double-fault case tested separately
    victims = _repairable_data_victims(arr, member=0, limit=6)
    assert victims, "no repairable victim chunks found"
    n_bad = 0
    for phys, z, off in victims:
        arr.drives[phys].corrupt_bit_rot(z, off, byte=1, bit=7)
        n_bad += 1
    res = arr.scrub_once()
    assert res["skipped_members"] > 0
    assert res["repaired"] > 0
    for lba, want in ref.items():
        assert np.array_equal(arr.read(lba, 1)[0], want), f"lba {lba}"
    arr.rebuild_drive(1)
    arr.scrub_once()
    for lba, want in ref.items():
        assert np.array_equal(arr.read(lba, 1)[0], want)


def test_unrepairable_double_fault_raises_loudly():
    """Data + parity lost in one raid5 stripe: verify-on-read and scrub
    both surface IntegrityError -- wrong bytes are never returned."""
    arr, _, _ = _mk("raid5")
    ref = _fill(arr)
    # find one user block and corrupt every member's copy of its stripe
    lba = 7
    from repro.core.l2p import NO_PBA, unpack_pba
    pba = arr.l2p.get(lba)
    assert pba != int(NO_PBA)
    seg_id, member, off = unpack_pba(pba)
    rec = arr.segments[seg_id]
    info = rec.info
    c = info.chunk_blocks
    chunk_idx = (off - info.data_start()) // c
    arr.drives[info.drive_ids[member]].corrupt_bit_rot(
        info.zone_ids[member], off, byte=0, bit=0
    )
    # kill every survivor copy of that stripe too (data and parity)
    seq, members = arr._chunk_members(rec, member, int(chunk_idx))
    killed = 0
    for d, cidx in members.items():
        if killed >= 2:
            break  # m=1: two extra losses guarantee < k intact
        z = info.zone_ids[d]
        arr.drives[info.drive_ids[d]].mark_unreadable(
            z, info.data_start() + cidx * c
        )
        killed += 1
    with pytest.raises(IntegrityError):
        arr.read(lba, 1)
    with pytest.raises(IntegrityError):
        arr.scrub_segment(seg_id)
    # other stripes still read clean
    for other in range(20, 30):
        assert np.array_equal(arr.read(other, 1)[0], ref[other])


# ------------------------------------------------- verify-on-read + cache


def test_verify_on_read_repairs_in_place():
    """A corrupt block hit by a foreground read is detected, reconstructed
    through parity, rewritten in place, and the counters advance."""
    arr, _, _ = _mk("raid5")
    ref = _fill(arr)
    from repro.core.l2p import unpack_pba
    lba = 42
    seg_id, member, off = unpack_pba(arr.l2p.get(lba))
    info = arr.segments[seg_id].info
    d = arr.drives[info.drive_ids[member]]
    z = info.zone_ids[member]
    d.corrupt_bit_rot(z, off, byte=9, bit=3)
    crc_before = int(d.crc[z, off])
    got = arr.read(lba, 1)[0]
    assert np.array_equal(got, ref[lba])
    assert arr.stats.integrity_corruptions_detected >= 1
    assert arr.stats.integrity_blocks_repaired >= 1
    # media healed: a raw read now matches the checksum store again
    assert int(crc32c_many(d.read(z, off, 1))[0]) == crc_before
    # scalar path too
    d.mark_unreadable(z, off)
    got = arr._read_block(lba)
    assert np.array_equal(got, ref[lba])
    assert not d.unc[z, off]


def test_repair_refreshes_warm_cache():
    """Cache coherence with repair: resident copies are refreshed when
    their block is repaired, fills only ever carry verified bytes, and a
    warm cache never serves pre-repair garbage."""
    from repro.cache import CacheConfig, ZnsCacheTier

    arr, cfg, _ = _mk("raid5")
    cache = ZnsCacheTier(
        CacheConfig(n_zones=4, zone_cap_blocks=64, block_bytes=BB,
                    admit_threshold=1),
        cfg.logical_blocks,
    )
    arr.attach_cache(cache)
    ref = _fill(arr)
    # warm the cache with every lba (repeat so the admission sketch sees
    # the keys as reused), then corrupt media underneath the warm copies
    for _ in range(3):
        for lba in ref:
            arr.read(lba, 1)
    assert cache.resident_count() > 0
    rng = np.random.default_rng(5)
    _inject_mixed(arr, rng, frac=0.05)
    arr.scrub_once()
    # every resident copy equals the repaired (reference) bytes
    served_from_cache = 0
    for lba, want in ref.items():
        row = cache.lookup_one(lba << 1)
        if row is not None:
            served_from_cache += 1
            assert np.array_equal(row, want), f"stale cache row for {lba}"
        assert np.array_equal(arr.read(lba, 1)[0], want)
    assert served_from_cache > 0
    assert arr.stats.integrity_blocks_repaired > 0


# ------------------------------------------------- fault plan + timed actor


def test_probabilistic_media_mix_plan_shape():
    """One seeded plan drives drive-failure cycles AND a weighted media
    mix; kinds follow the weights, events stay inside the horizon, and
    the same seed reproduces the same plan."""
    mix = {"bit_rot": 3.0, "unreadable": 1.0, "misdirected_write": 1.0,
           "torn_write": 0.5}
    kw = dict(n_drives=4, horizon_us=200_000.0, mtbf_us=60_000.0,
              repair_after_us=5_000.0, seed=99, media_mix=mix,
              media_mtbf_us=1_500.0)
    plan = FaultPlan.probabilistic(**kw)
    plan2 = FaultPlan.probabilistic(**kw)
    assert [(e.t_us, e.kind, e.drive) for e in plan.events] == \
           [(e.t_us, e.kind, e.drive) for e in plan2.events]
    kinds = [e.kind for e in plan.events]
    assert "fail" in kinds and "rebuild" in kinds
    media = [k for k in kinds if k in MEDIA_KINDS]
    assert len(media) > 20
    assert media.count("bit_rot") > media.count("torn_write")
    assert all(0 <= e.t_us for e in plan.events)
    assert all(e.t_us < 200_000.0 + 5_000.0 for e in plan.events)
    with pytest.raises(ValueError):
        FaultPlan.probabilistic(n_drives=4, horizon_us=1e5, seed=1,
                                media_mix={"bogus": 1.0},
                                media_mtbf_us=100.0)
    with pytest.raises(ValueError):
        FaultPlan.probabilistic(n_drives=4, horizon_us=1e5, seed=1,
                                media_mix={"bit_rot": 1.0})


def _timed_pipe(scheme="raid5", seed=0, logical=128, zones=10, n_drives=4,
                **cfg_kw):
    cfg_kw.setdefault("verify_reads", True)
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=4,
                        chunk_blocks=1, logical_blocks=logical,
                        gc_free_segments_low=1, **cfg_kw)
    zns = ZnsConfig(n_zones=zones, zone_cap_blocks=64, block_bytes=BB)
    return HandlerPipeline.build_timed(cfg, zns, seed=seed,
                                       flush_interval_us=200.0)


def test_timed_scrub_actor_heals_under_load():
    """Scripted media faults land mid-write-stream; the paced scrub actor
    walks the sealed segments on the virtual clock, books device time
    (``notes["scrub_device_us"]``), repairs everything it finds, and the
    drained array reads the reference."""
    pipe = _timed_pipe()
    # victims pinned to distinct stripe groups of zone 0 (group span 4,
    # data start 1) so no stripe ever takes two faults -- a raid5 stripe
    # with two losses is the separately-tested unrepairable case
    plan = FaultPlan.scripted([
        FaultEvent(t_us=t, kind=kind, drive=d, zone=0, off=off)
        for t, kind, d, off in [
            (900.0, "bit_rot", 0, 5), (1400.0, "unreadable", 2, 9),
            (1900.0, "bit_rot", 3, 13), (2400.0, "misdirected_write", 1, 17),
        ]
    ])
    inj = pipe.attach_faults(plan, seed=4)
    rng = np.random.default_rng(5)
    ref = {}
    t = 0.0
    for _ in range(4):
        for lba in range(0, 128, 2):
            blk = rng.integers(0, 256, (2, BB), dtype=np.uint8)
            pipe.submit_write(lba, blk, at=t)
            ref[lba], ref[lba + 1] = blk[0].copy(), blk[1].copy()
            t += 8.0
    pipe.schedule_scrub(at=t + 500.0, interval_us=50.0)
    pipe.drain()
    assert len(inj.log) > 0
    assert pipe.array.stats.integrity_scrub_passes >= 1
    assert pipe.recorder.notes.get("scrub_device_us", 0.0) > 0.0
    # faults on sealed media were repaired by the scrub (open-zone hits
    # are healed by verify-on-read when touched)
    for lba, want in ref.items():
        assert np.array_equal(pipe.array.read(lba, 1)[0], want), f"lba {lba}"


def _sealed_pipe(verify):
    """A timed raid5 array with two overwrite rounds on it, so several
    segments are sealed."""
    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8,
                        chunk_blocks=1, logical_blocks=256,
                        gc_free_segments_low=1, verify_reads=verify)
    zns = ZnsConfig(n_zones=16, zone_cap_blocks=64, block_bytes=BB)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=9,
                                       flush_interval_us=200.0)
    rng = np.random.default_rng(9)
    writes = [(lba, rng.integers(0, 256, (1, BB), dtype=np.uint8))
              for _ in range(2) for lba in range(256)]
    pipe.precondition(writes)
    return pipe, {lba: blk[0] for lba, blk in writes}


def _read_load(t0=0.0):
    import dataclasses

    from repro.sim import TenantSpec, multi_tenant

    load = multi_tenant([
        TenantSpec(name="reader", kind="uniform", n_ops=300,
                   rate_iops=50_000, read_frac=1.0, seed=31),
    ], logical_blocks=256)
    return [dataclasses.replace(r, t_us=r.t_us + t0) for r in load]


def _corrupt_one_per_group(arr, rng):
    """One bit-rot hit in every stripe group of every sealed segment,
    cycling the victim member: a storm, yet one loss per stripe, so raid5
    repairs all of it.  Returns the number of blocks hit."""
    from repro.core.segment import SegmentState

    n_bad = 0
    for rec in sorted(arr.segments.values(), key=lambda r: r.info.seg_id):
        info = rec.info
        if info.state != int(SegmentState.SEALED):
            continue
        span = max(1, info.group_size) * info.chunk_blocks
        n_groups = -(-info.n_stripes * info.chunk_blocks // span)
        for g in range(n_groups):
            m = g % info.n_drives
            d = arr.drives[info.drive_ids[m]]
            zone = info.zone_ids[m]
            off = info.data_start() + g * span + int(rng.integers(0, span))
            if off >= int(d.wp[zone]):
                continue
            d.corrupt_bit_rot(zone, off, int(rng.integers(0, BB)),
                              int(rng.integers(0, 8)))
            n_bad += 1
    return n_bad


def test_timed_scrub_pass_over_clean_media():
    """A paced scrub pass over clean sealed media verifies blocks, books
    device time for them, and finds and repairs nothing."""
    pipe, _ = _sealed_pipe(verify=True)
    pipe.schedule_scrub(at=pipe.engine.now + 10.0, interval_us=20.0)
    pipe.drain()
    st = pipe.array.stats
    assert st.integrity_scrub_passes >= 1
    assert st.integrity_scrub_blocks > 0
    assert pipe.recorder.notes.get("scrub_device_us", 0.0) > 0.0
    assert st.integrity_blocks_repaired == 0


def test_verify_on_read_costs_under_ten_percent_of_read_p99():
    """Verifying every read's checksums adds less than 10% to the read
    p99 of the same seeded load (virtual time)."""
    off = _sealed_pipe(verify=False)[0].replay(_read_load())
    on = _sealed_pipe(verify=True)[0].replay(_read_load())
    p_off, p_on = off.percentiles(op="R"), on.percentiles(op="R")
    assert p_on["n"] == p_off["n"] == 300
    assert p_on["p99"] < p_off["p99"] * 1.10, (p_on, p_off)


def test_timed_scrub_repairs_corruption_storm_under_read_load():
    """Bit rot in every stripe group of every sealed segment; the paced
    scrub repairs each hit while a read load runs, and every block then
    reads back as written."""
    pipe, ref = _sealed_pipe(verify=True)
    n_bad = _corrupt_one_per_group(pipe.array, np.random.default_rng(13))
    assert n_bad > 0
    pipe.schedule_scrub(at=pipe.engine.now + 10.0, interval_us=50.0)
    rec = pipe.replay(_read_load(pipe.engine.now))
    assert rec.percentiles(op="R")["n"] == 300
    assert pipe.array.stats.integrity_blocks_repaired == n_bad
    for lba, want in ref.items():
        assert np.array_equal(pipe.array.read(lba, 1)[0], want), f"lba {lba}"


def test_timed_mixed_plan_failures_and_media():
    """The acceptance-style timed run: one probabilistic plan fires a
    drive failure/rebuild cycle and a media-fault mix over the same
    horizon -- media faults land *during* the outage, which is why the
    array is raid6 (a second loss per stripe must stay repairable);
    scrub + verify-on-read keep every read correct and no reader ever
    sees wrong bytes."""
    plan = FaultPlan.probabilistic(
        n_drives=5, horizon_us=3500.0, mtbf_us=1_500.0,
        repair_after_us=900.0, seed=21, rebuild_interval_us=30.0,
        media_mix={"bit_rot": 2.0, "unreadable": 1.0}, media_mtbf_us=400.0,
    )
    assert any(e.kind == "fail" for e in plan.events)
    assert any(e.kind in MEDIA_KINDS for e in plan.events)
    pipe = _timed_pipe("raid6", n_drives=5)
    inj = pipe.attach_faults(plan, seed=2)
    rng = np.random.default_rng(8)
    ref = {}
    t = 0.0
    for _ in range(4):
        for lba in range(0, 128, 2):
            blk = rng.integers(0, 256, (2, BB), dtype=np.uint8)
            pipe.submit_write(lba, blk, at=t)
            ref[lba], ref[lba + 1] = blk[0].copy(), blk[1].copy()
            t += 8.0
    pipe.drain()
    assert not any(d.failed for d in pipe.array.drives)
    pipe.array.scrub_once()
    fired = {k for _, k, _ in inj.log}
    assert fired & set(MEDIA_KINDS)
    for lba, want in ref.items():
        assert np.array_equal(pipe.array.read(lba, 1)[0], want), f"lba {lba}"


# ----------------------------------------------- recovery winner resolution


def test_recovery_corrupt_header_loses_to_intact_copy():
    """A rotted header replica must not decide segment geometry: the scan
    skips it (media checksum) and installs from an intact member."""
    for batched in (True, False):
        arr, cfg, zns = _mk("raid5", **{"batched": batched})
        ref = _fill(arr)
        rec = next(iter(arr.segments.values()))
        info = rec.info
        d = arr.drives[info.drive_ids[0]]
        d.corrupt_bit_rot(info.zone_ids[0], 0, byte=10, bit=1)  # header block
        arr2 = recover_array(arr.drives, cfg, zns)
        assert info.seg_id in arr2.segments, "segment lost to a rotted header"
        got = arr2.segments[info.seg_id].info
        assert got.zone_ids == info.zone_ids
        for lba, want in ref.items():
            assert np.array_equal(arr2.read(lba, 1)[0], want)


def test_recovery_corrupt_footer_falls_back_to_oob():
    """A sealed segment whose footer rotted on one member: recovery takes
    the OOB-area scan for that member instead of installing garbage
    mappings, and every winner still resolves correctly."""
    for batched in (True, False):
        arr, cfg, zns = _mk("raid5", **{"batched": batched})
        ref = _fill(arr)
        from repro.core.segment import SegmentState
        rec = next(r for r in arr.segments.values()
                   if r.info.state == int(SegmentState.SEALED))
        info = rec.info
        foot_start = info.data_start() + info.n_stripes * info.chunk_blocks
        d = arr.drives[info.drive_ids[1]]
        z = info.zone_ids[1]
        assert int(d.wp[z]) > foot_start
        d.corrupt_bit_rot(z, foot_start, byte=2, bit=5)
        arr2 = recover_array(arr.drives, cfg, zns)
        for lba, want in ref.items():
            assert np.array_equal(arr2.read(lba, 1)[0], want)


# ----------------------------------------------------- footer fuzz (hypothesis)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=60), st.randoms())
def test_footer_roundtrip_fuzz(n_entries, rnd):
    """pack/unpack roundtrips under truncation and corruption: equality on
    clean footers, FooterError (never garbage mappings) on truncated or
    checksum-failing ones."""
    assert footer_has_crc(BB)
    rng = np.random.default_rng(rnd.randint(0, 1 << 30))
    entries = np.zeros(n_entries, dtype=OOB_DTYPE)
    entries["lba"] = rng.integers(0, 1 << 40, n_entries).astype(np.uint64)
    entries["ts"] = rng.integers(0, 1 << 40, n_entries).astype(np.uint64)
    entries["stripe"] = rng.integers(0, 1 << 20, n_entries).astype(np.uint32)
    blocks = pack_footer(entries, BB)
    back = unpack_footer(blocks, n_entries, BB, strict=True)
    assert (back == entries).all()
    # truncation: drop the last block when entries spill past one block
    if blocks.shape[0] > 1:
        with pytest.raises(FooterError):
            unpack_footer(blocks[:-1], n_entries, BB, strict=False)
    # corruption in the entry area: strict unpack refuses
    epb = footer_entries_per_block(BB)
    bad = blocks.copy()
    byte = int(rng.integers(0, epb * 20))
    bad[int(rng.integers(0, bad.shape[0])), byte] ^= 0x40
    with pytest.raises(FooterError):
        unpack_footer(bad, n_entries, BB, strict=True)
    # blocks too narrow to hold even one entry row
    with pytest.raises(FooterError):
        unpack_footer(np.zeros((1, 16), np.uint8), 1, BB)


# --------------------------------------- ROADMAP: capacity-tight manual GC


def test_manual_gc_capacity_tight_keeps_restage_zone():
    """ROADMAP known issue: manual-GC configs (``gc_free_segments_low=0``)
    on capacity-tight geometry driven to the edge.  The PR 9 1-zone open
    floor must leave ``gc_once`` a restage destination: foreground opens
    stop with a loud RuntimeError instead of eating the last zone, and a
    manual GC pass still runs and frees space."""
    arr, _, _ = _mk("raid5", logical=96, zones=5, zone_cap=32,
                    gc_free_segments_low=0)
    assert arr.reserved_zones() == 1  # the manual-GC fallback floor
    rng = np.random.default_rng(1)
    blocked = False
    for i in range(2000):
        lba = int(rng.integers(0, 96))
        blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        try:
            arr.write(lba, blk)
        except RuntimeError as e:
            assert "out of free zones" in str(e)
            blocked = True
            break
    assert blocked, "geometry never reached the capacity edge"
    # the floor kept a restage zone: manual GC can still make progress
    # (no deadlock opening its destination segment)
    freed = arr.gc_once()
    assert freed, "manual gc_once made no progress at the capacity edge"
    arr.write(0, rng.integers(0, 256, (1, BB), dtype=np.uint8))
    arr.flush()
