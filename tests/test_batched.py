"""Batched (stripe-group) datapath: bit-identity with the per-stripe path.

The group-level codec (`encode_batch_np`/`decode_batch_np`), the batched
Pallas kernels behind it, and the array's `batched=True` datapath must all be
byte-for-byte equivalent to the per-stripe/per-block legacy path -- including
degraded decode for every surviving-role subset and non-multiple-of-128 lane
counts (the padding path).  See DESIGN.md §2-3.
"""
import itertools

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.array import Stats, ZapRaidConfig, ZapRAIDArray
from repro.core.l2p import NO_PBA, L2PTable, pack_pba, unpack_pba, unpack_pba_many
from repro.core.raid import (
    StripeCodec,
    decode_meta,
    decode_meta_batch,
    make_scheme,
    parity_oob,
    parity_oob_batch,
)
from repro.core.zns import ZnsConfig
from repro.kernels import ops, ref
from repro.obs import HostSpans

BB = 256
SCHEMES = [("raid0", 4), ("raid01", 4), ("raid4", 4), ("raid5", 4), ("raid6", 5)]


def _codec(name, n_drives):
    return StripeCodec(make_scheme(name, n_drives), use_pallas=True, interpret=True)


def _mirror_ok(scheme, surv):
    """RAID-01 can only decode when every chunk has at least one copy left."""
    return len({r % scheme.k for r in surv}) == scheme.k


# ------------------------------------------------------------ kernel level

@pytest.mark.parametrize("s_count", [1, 3, 8])
@pytest.mark.parametrize("n", [128, 2048, 25])  # 25: unaligned lanes (padding)
def test_parity_xor_batch_matches_per_stripe(s_count, n):
    rng = np.random.default_rng(s_count * n)
    data = jnp.asarray(
        rng.integers(-(2**31), 2**31, (s_count, 4, n), dtype=np.int64), jnp.int32
    )
    got = ops.xor_parity_batch(data, use_pallas=True, interpret=True)
    per = jnp.stack([ops.xor_parity(data[s], use_pallas=True)
                     for s in range(s_count)])
    assert jnp.array_equal(got, per)
    assert np.array_equal(
        np.asarray(got), np.bitwise_xor.reduce(np.asarray(data), axis=1)
    )
    # jnp oracle path agrees too
    assert jnp.array_equal(
        ops.xor_parity_batch(data, use_pallas=False), got
    )


@pytest.mark.parametrize("k,m", [(2, 1), (3, 2), (4, 3)])
def test_gf256_matmul_batch_matches_per_stripe(k, m):
    rng = np.random.default_rng(k * 31 + m)
    data = jnp.asarray(
        rng.integers(-(2**31), 2**31, (5, k, 512), dtype=np.int64), jnp.int32
    )
    got = ops.rs_encode_batch(data, m, use_pallas=True, interpret=True)
    per = jnp.stack([ops.rs_encode(data[s], m, use_pallas=True)
                     for s in range(5)])
    assert jnp.array_equal(got, per)
    assert jnp.array_equal(ops.rs_encode_batch(data, m, use_pallas=False), got)


def test_rs_decode_batch_roundtrip():
    rng = np.random.default_rng(7)
    k, m = 3, 2
    data = jnp.asarray(
        rng.integers(-(2**31), 2**31, (4, k, 256), dtype=np.int64), jnp.int32
    )
    parity = ops.rs_encode_batch(data, m, use_pallas=True)
    code = jnp.concatenate([data, parity], axis=1)
    for surv in itertools.combinations(range(k + m), k):
        rec = ops.rs_decode_batch(code[:, list(surv)], surv, k, m,
                                  use_pallas=True)
        assert jnp.array_equal(rec, data), surv


def test_batch_refs_match_kernels():
    rng = np.random.default_rng(8)
    data = jnp.asarray(
        rng.integers(-(2**31), 2**31, (3, 4, 384), dtype=np.int64), jnp.int32
    )
    assert jnp.array_equal(
        ref.parity_xor_batch_ref(data),
        jnp.stack([ref.parity_xor_ref(data[s]) for s in range(3)]),
    )
    coeff = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 7, 8]]), jnp.int32)
    assert jnp.array_equal(
        ref.gf256_matmul_batch_ref(coeff, data),
        jnp.stack([ref.gf256_matmul_ref(coeff, data[s]) for s in range(3)]),
    )


# ------------------------------------------------------------- codec level

@pytest.mark.parametrize("scheme,n_drives", SCHEMES)
@pytest.mark.parametrize("nbytes", [512, 96])  # 96 bytes = 24 lanes: padding
def test_encode_batch_bit_identical(scheme, n_drives, nbytes):
    codec = _codec(scheme, n_drives)
    k = codec.scheme.k
    rng = np.random.default_rng(hash((scheme, nbytes)) % (1 << 31))
    for s_count in (1, 3, 7):  # non-power-of-two exercises batch padding
        data = rng.integers(0, 256, (s_count, k, nbytes), dtype=np.uint8)
        batch = codec.encode_batch_np(data)
        per = np.stack([codec.encode_np(data[s]) for s in range(s_count)])
        assert batch.shape == (s_count, codec.scheme.m, nbytes)
        assert np.array_equal(batch, per.reshape(batch.shape))


@pytest.mark.parametrize("scheme,n_drives", SCHEMES[1:])  # raid0 cannot decode
@pytest.mark.parametrize("nbytes", [512, 96])
@pytest.mark.parametrize("permuted", [False, True])  # survivors in every order
def test_decode_batch_every_survivor_subset(scheme, n_drives, nbytes, permuted):
    """Every survivor subset decodes to the data, batched and per stripe, one
    copy each way per ``decode_np``.  A single-parity stripe that lost a data
    role brings back only the rebuilt row: n bytes, not k * n."""
    codec = _codec(scheme, n_drives)
    sch = codec.scheme
    rng = np.random.default_rng(hash((scheme, nbytes, "d")) % (1 << 31))
    s_count = 4
    data = rng.integers(0, 256, (s_count, sch.k, nbytes), dtype=np.uint8)
    code = np.concatenate([data, codec.encode_batch_np(data)], axis=1)
    tested = 0
    for subset in itertools.combinations(range(sch.n), sch.k):
        if sch.mirror and not _mirror_ok(sch, subset):
            continue
        lost_data = set(range(sch.k)) - set(subset)
        back = nbytes if sch.m == 1 and lost_data else sch.k * nbytes
        orders = itertools.permutations(subset) if permuted else [subset]
        for surv in orders:
            batch = codec.decode_batch_np(code[:, list(surv)], surv)
            codec.copy_stats = st = Stats()
            per = np.stack(
                [codec.decode_np(code[s][list(surv)], surv) for s in range(s_count)]
            )
            codec.copy_stats = None
            assert (st.h2d_copies, st.h2d_bytes, st.d2h_copies, st.d2h_bytes) == (
                s_count, s_count * sch.k * nbytes, s_count, s_count * back), surv
            assert per.dtype == np.uint8
            assert np.array_equal(batch, per.reshape(batch.shape)), (scheme, surv)
            assert np.array_equal(batch.reshape(s_count, sch.k, nbytes), data), surv
            tested += 1
    assert tested > 1


@pytest.mark.parametrize("scheme,n_drives", SCHEMES[1:])
def test_oob_meta_batch_bit_identical(scheme, n_drives):
    codec = _codec(scheme, n_drives)
    sch = codec.scheme
    rng = np.random.default_rng(hash((scheme, "meta")) % (1 << 31))
    s_count, c = 5, 2
    lbas = rng.integers(0, 1 << 40, (s_count, sch.k, c)).astype(np.uint64)
    ts = rng.integers(0, 1 << 40, (s_count, sch.k, c)).astype(np.uint64)
    p_lba, p_ts = parity_oob_batch(codec, lbas, ts)
    for s in range(s_count):
        pl, pt = parity_oob(codec, lbas[s], ts[s])
        assert np.array_equal(p_lba[s], pl) and np.array_equal(p_ts[s], pt)
    # decode side: drop data role 0, keep the rest + first parity
    surv = tuple(range(1, sch.k)) + (sch.k,)
    if sch.mirror and not _mirror_ok(sch, surv):
        return
    full_lba = np.concatenate([lbas, p_lba], axis=1)
    full_ts = np.concatenate([ts, p_ts], axis=1)
    d_lba, d_ts = decode_meta_batch(
        codec, full_lba[:, list(surv)], full_ts[:, list(surv)], surv
    )
    for s in range(s_count):
        dl, dt = decode_meta(
            codec, full_lba[s][list(surv)], full_ts[s][list(surv)], surv
        )
        assert np.array_equal(d_lba[s], dl) and np.array_equal(d_ts[s], dt)
    assert np.array_equal(d_lba, lbas) and np.array_equal(d_ts, ts)


@pytest.mark.parametrize("scheme", ["raid5", "raid4"])
def test_single_parity_decode_np_one_issue_span_one_xor(scheme, monkeypatch):
    """Each single-parity ``decode_np`` opens one ``codec:issue`` span keyed
    ``("decode", ((k, n),))`` and calls ``ops.xor_parity`` once, through the
    module attribute, on the (k, n) survivors."""
    codec = _codec(scheme, 4)
    k, nbytes = codec.scheme.k, 512
    data = np.random.default_rng(7).integers(0, 256, (k, nbytes), dtype=np.uint8)
    code = np.concatenate([data, codec.encode_np(data)])
    # every lost data role, the k survivors in every order
    orders = [
        order
        for lost in range(k)
        for order in itertools.permutations([r for r in range(k + 1) if r != lost])
    ]
    calls = []
    xor = ops.xor_parity

    def counting(*args, **kw):
        calls.append(tuple(tuple(a.shape) for a in args if hasattr(a, "shape")))
        return xor(*args, **kw)

    monkeypatch.setattr(ops, "xor_parity", counting)
    rec = HostSpans(annotate=False).install()
    try:
        for order in orders:
            codec.decode_np(code[list(order)], order)
        snap = rec.snapshot()
    finally:
        rec.uninstall()
    shapes = ((k, nbytes // 4),)
    assert snap["spans"]["codec:issue"]["count"] == len(orders)
    assert snap["dispatches"] == {("decode", shapes): len(orders)}
    assert calls == [shapes] * len(orders)


# ---------------------------------------------------------------- L2P level

@pytest.mark.parametrize("limit", [None, 64])
def test_l2p_get_set_many_equivalent(limit):
    written = {}

    def wcb(gid, entries):
        written[gid] = entries.copy()

    def rcb(gid):
        return written.get(gid)

    t = L2PTable(512, memory_limit_entries=limit,
                 write_mapping_block=wcb, read_mapping_block=rcb,
                 entries_per_group=32)
    rng = np.random.default_rng(0)
    lbas = rng.integers(0, 512, 200)
    pbas = np.array([pack_pba(int(l) % 7, int(l) % 4, int(l)) for l in lbas])
    t.set_many(lbas, pbas)
    got = t.get_many(lbas)
    want = np.array([t.get(int(l)) for l in lbas])
    assert np.array_equal(got, want)
    # later duplicates win, like a sequential set loop
    t.set_many(np.array([5, 5]), np.array([111, 222]))
    assert t.get(5) == 222
    # unmapped stays NO_PBA
    t2 = L2PTable(64, entries_per_group=32)
    assert np.all(t2.get_many(np.arange(64)) == int(NO_PBA))


def test_l2p_set_survives_clock_eviction_pressure():
    """A store into a just-faulted group must not be lost when the CLOCK hand
    would evict that very group (the faulting group is pinned)."""
    written = {}
    t = L2PTable(24, memory_limit_entries=4,
                 write_mapping_block=lambda g, e: written.__setitem__(g, e.copy()),
                 read_mapping_block=written.get,
                 entries_per_group=4)  # limit = 1 resident group
    for _ in range(3):  # pump gid 1's refbit so the sweep has to pass it twice
        t.get(4)
    t.set_many(np.array([0]), np.array([777]))
    assert t.get(0) == 777
    t.set(9, 555)  # scalar path under the same pressure
    assert t.get(9) == 555
    t.flush()


def test_unpack_pba_many_matches_scalar():
    pbas = np.array([pack_pba(s, d, o) for s, d, o in
                     [(0, 0, 0), (5, 3, 77), (4095, 15, 65535)]])
    segs, drives, offs = unpack_pba_many(pbas)
    for i, p in enumerate(pbas):
        s, d, o = unpack_pba(int(p))
        assert (segs[i], drives[i], offs[i]) == (s, d, o)


# ------------------------------------------------------------ system level

def _run_workload(batched, scheme="raid5", seed=3, n_writes=200, **kw):
    rng = np.random.default_rng(seed)
    cfg = ZapRaidConfig(scheme=scheme, n_drives=4, group_size=8, chunk_blocks=1,
                        logical_blocks=256, gc_free_segments_low=1,
                        batched=batched, **kw)
    zns = ZnsConfig(n_zones=12, zone_cap_blocks=64, block_bytes=BB)
    arr = ZapRAIDArray(cfg, zns)
    ref_data = {}
    for _ in range(n_writes):
        n = int(rng.integers(1, 4))
        lba = int(rng.integers(0, 256 - n))
        blk = rng.integers(0, 256, (n, BB), dtype=np.uint8)
        arr.write(lba, blk)
        for j in range(n):
            ref_data[lba + j] = blk[j].copy()
    arr.flush()
    return arr, ref_data


@pytest.mark.parametrize("scheme", ["raid0", "raid01", "raid5", "raid6"])
def test_batched_array_media_identical_to_legacy(scheme):
    """Same workload, batched vs legacy datapath -> identical drive media."""
    a1, ref1 = _run_workload(True, scheme)
    a0, ref0 = _run_workload(False, scheme)
    assert ref1.keys() == ref0.keys()
    for d1, d0 in zip(a1.drives, a0.drives):
        assert np.array_equal(d1.data, d0.data)
        assert np.array_equal(d1.oob, d0.oob)
        assert np.array_equal(d1.wp, d0.wp)


def test_batched_multiblock_read_matches_per_block():
    arr, ref_data = _run_workload(True)
    got = arr.read(0, 64)
    for i in range(64):
        want = ref_data.get(i, np.zeros(BB, np.uint8))
        assert np.array_equal(got[i], want), i


def test_batched_degraded_read_and_rebuild_media_identical():
    a1, ref1 = _run_workload(True)
    a0, _ = _run_workload(False)
    for a in (a1, a0):
        a.fail_drive(1)
    for lba, want in ref1.items():
        assert np.array_equal(a1.read(lba, 1)[0], want)
    a1.rebuild_drive(1)
    a0.rebuild_drive(1)
    for d1, d0 in zip(a1.drives, a0.drives):
        assert np.array_equal(d1.data, d0.data)
        assert np.array_equal(d1.oob, d0.oob)
    for lba, want in ref1.items():
        assert np.array_equal(a1.read(lba, 1)[0], want)


def test_batched_raid6_double_failure_rebuild():
    arr, ref_data = _run_workload(True, scheme="raid6", n_writes=150)
    arr.fail_drive(0)
    arr.fail_drive(2)
    for lba, want in ref_data.items():
        assert np.array_equal(arr.read(lba, 1)[0], want)
    arr.rebuild_drive(0)
    arr.rebuild_drive(2)
    before = arr.stats.degraded_reads
    for lba, want in ref_data.items():
        assert np.array_equal(arr.read(lba, 1)[0], want)
    assert arr.stats.degraded_reads == before


def test_batched_gc_preserves_logical_contents():
    rng = np.random.default_rng(9)
    cfg = ZapRaidConfig(scheme="raid5", n_drives=4, group_size=8, chunk_blocks=1,
                        logical_blocks=96, gc_free_segments_low=2, batched=True)
    zns = ZnsConfig(n_zones=6, zone_cap_blocks=64, block_bytes=BB)
    arr = ZapRAIDArray(cfg, zns)
    ref_data = {}
    for _ in range(1200):
        lba = int(rng.integers(0, 96))
        blk = rng.integers(0, 256, (1, BB), dtype=np.uint8)
        arr.write(lba, blk)
        ref_data[lba] = blk[0].copy()
    arr.flush()
    assert arr.stats.gc_runs > 0
    for lba, want in ref_data.items():
        assert np.array_equal(arr.read(lba, 1)[0], want)


def test_batched_write_supersedes_buffered_duplicate():
    """A bulk append must cancel a still-buffered older copy of the same LBA."""
    arr, _ = _run_workload(True, n_writes=0)
    rng = np.random.default_rng(11)
    a = rng.integers(0, 256, (1, BB), dtype=np.uint8)
    b = rng.integers(0, 256, (3, BB), dtype=np.uint8)
    arr.write(7, a)        # buffered in the open append group
    arr.write(6, b)        # covers LBAs 6,7,8: supersedes the buffered 7
    arr.flush()
    assert np.array_equal(arr.read(7, 1)[0], b[1])
    assert np.array_equal(arr.read(6, 1)[0], b[0])
    assert np.array_equal(arr.read(8, 1)[0], b[2])
