"""Async block-device service tier (PR 6).

Covers:

* token-bucket arithmetic on the virtual clock;
* submission/completion ordering: acks fire at device-completion times,
  the shared CQ collects every finished request, read payloads round-trip;
* bit-identity: the same workload through the service vs direct pipeline
  calls leaves identical media, OOB, and read-back;
* QoS: strict-priority isolation of a latency tenant under an aggressor
  and of serving reads under checkpoint saves (p99 separation vs FIFO),
  EDF ordering within a class, admission rejection at the queue cap,
  token-bucket shaping;
* closed-loop read IOPS that grow with queue depth and saturate;
* closed-loop driver: the window bounds outstanding requests;
* the drained-queue flush fix: a lone service write completes from
  ``engine.run()`` alone via self-re-arming timeout-flush ticks;
* per-tenant queue-wait vs service-time stage accounting;
* async checkpoint save/restore through the service, including the
  degraded-lane restore path and manifest-after-extents crash ordering.
"""
import numpy as np
import pytest

from repro.core.array import ZapRaidConfig
from repro.core.handlers import HandlerPipeline
from repro.core.zns import ZnsConfig
from repro.service import (
    DONE,
    LATENCY,
    REJECTED,
    BlockDeviceService,
    ClosedLoopClient,
    QosClass,
    TokenBucket,
)
from repro.sim import TenantSpec, multi_tenant, synthetic


def _timed_pipe(scheme="raid5", group_size=4, seed=0, logical_blocks=128,
                **cfg_kw):
    cfg = ZapRaidConfig(scheme=scheme, n_drives=4, group_size=group_size,
                        chunk_blocks=1, logical_blocks=logical_blocks,
                        gc_free_segments_low=1, **cfg_kw)
    zns = ZnsConfig(n_zones=8, zone_cap_blocks=64, block_bytes=256)
    return HandlerPipeline.build_timed(cfg, zns, seed=seed,
                                       flush_interval_us=200.0)


def _precondition(pipe, n_blocks, seed=1):
    rng = np.random.default_rng(seed)
    pipe.precondition(
        (lba, rng.integers(0, 256, (1, 256), dtype=np.uint8))
        for lba in range(n_blocks)
    )


# ------------------------------------------------------------- token bucket


def test_token_bucket_arithmetic():
    tb = TokenBucket(rate_iops=10_000.0, burst=4, t0=0.0)  # 1 token / 100us
    assert tb.peek(0.0) == 4.0
    for _ in range(4):
        assert tb.take(0.0)
    assert not tb.take(0.0)
    assert tb.next_ready(0.0) == pytest.approx(100.0)
    assert tb.peek(50.0) == pytest.approx(0.5)
    assert tb.take(100.0)
    # refill caps at burst
    assert tb.peek(1e9) == 4.0


# ------------------------------------------- submission/completion ordering


def test_acks_fire_at_device_times_and_cq_collects():
    pipe = _timed_pipe()
    svc = BlockDeviceService(pipe, max_inflight=64)
    svc.register("t", QosClass("t"))
    rng = np.random.default_rng(0)
    ref, done = {}, []
    t = 0.0
    for lba in range(24):
        blk = rng.integers(0, 256, (1, 256), dtype=np.uint8)
        ref[lba] = blk[0].copy()
        t += 20.0
        svc.submit_write("t", lba, blk, at=t, cb=done.append)
    svc.drain()
    assert len(done) == 24 and all(r.status == DONE for r in done)
    # acks fire on the virtual timeline, strictly after submission, and the
    # engine clock advanced to the last device completion
    assert all(r.t_done > r.t_submit for r in done)
    assert pipe.engine.now >= max(r.t_done for r in done)

    got = {}
    for lba in range(24):
        svc.submit_read("t", lba, 1,
                        cb=lambda r, l=lba: got.__setitem__(l, r.result[0]))
    svc.drain()
    assert all(np.array_equal(got[l], v) for l, v in ref.items())
    # every completion (48) went through the shared CQ in completion order
    reaped = svc.cq.drain()
    assert len(reaped) == 48 and svc.cq.pushed == 48
    assert all(reaped[i].t_done <= reaped[i + 1].t_done
               for i in range(len(reaped) - 1))
    assert len(svc.cq) == 0


def test_service_media_bit_identical_to_direct_calls():
    """The service is a pure scheduling layer: an identical workload through
    it vs direct pipeline calls must leave identical drive media, OOB, write
    pointers, and read-back."""
    rng = np.random.default_rng(7)
    ops = [(int(rng.integers(0, 120)),
            rng.integers(0, 256, (1, 256), dtype=np.uint8))
           for _ in range(48)]  # 48 blocks = 4 exactly-full groups (k=3)

    direct = _timed_pipe(seed=3)
    t = 0.0
    for lba, data in ops:
        t += 15.0
        direct.submit_write(lba, data, at=t)
    direct.drain()

    served = _timed_pipe(seed=3)
    svc = BlockDeviceService(served, max_inflight=64)
    svc.register("t", QosClass("t"))
    t = 0.0
    for lba, data in ops:
        t += 15.0
        svc.submit_write("t", lba, data, at=t)
    svc.drain()

    for d1, d2 in zip(direct.array.drives, served.array.drives):
        np.testing.assert_array_equal(d1.data, d2.data)
        np.testing.assert_array_equal(d1.oob, d2.oob)
        np.testing.assert_array_equal(d1.wp, d2.wp)
    ref = {}
    for lba, data in ops:
        ref[lba] = data[0]
    for lba, want in ref.items():
        np.testing.assert_array_equal(direct.array.read(lba, 1)[0], want)
        np.testing.assert_array_equal(served.array.read(lba, 1)[0], want)


# ----------------------------------------------------------------- QoS


def _victim_p99(policy):
    pipe = _timed_pipe(seed=5)
    _precondition(pipe, 128)
    svc = BlockDeviceService(pipe, max_inflight=8, policy=policy)
    svc.register("victim", LATENCY)
    svc.register("aggr", QosClass("ckpt", priority=2, max_inflight=4))
    for i in range(60):
        svc.submit_read("victim", (i * 7) % 128, at=50.0 * i)
    aggr = synthetic(
        TenantSpec(name="aggr", kind="uniform", n_ops=300, n_blocks=4,
                   arrival="closed", window=64, seed=2),
        120,
    )
    ClosedLoopClient(svc, "aggr", aggr, window=64).start(0.0)
    svc.drain()
    return svc.recorder.percentiles(op="R", tenant="victim")["p99"]


def test_qos_isolates_latency_tenant_from_aggressor():
    p99_fifo = _victim_p99("fifo")
    p99_qos = _victim_p99("qos")
    assert p99_qos * 2.0 <= p99_fifo


def test_checkpoint_under_serving_qos_beats_fifo():
    """Checkpoint saves streaming beside serving reads: QoS keeps the
    serving read p99 at least 2x below FIFO's, and both policies restore
    the checkpoint bit-identical."""
    from repro.service.scenario import checkpoint_under_serving

    qos = checkpoint_under_serving(policy="qos")
    fifo = checkpoint_under_serving(policy="fifo")
    assert qos["restore_ok"] and fifo["restore_ok"]
    assert qos["serve_p99_us"] * 2.0 <= fifo["serve_p99_us"], (qos, fifo)


def test_read_qd_sweep_iops_grow_with_depth_and_saturate():
    """Closed-loop reads: virtual IOPS rise with queue depth, and going
    from QD 16 to 32 buys less than going from 1 to 4, once the drives'
    channels fill."""
    from repro.service.scenario import read_qd_sweep

    rows = read_qd_sweep(qds=(1, 4, 16, 32), n_ops=96)
    iops = [r["virtual_iops"] for r in rows]
    assert iops == sorted(iops) and len(set(iops)) == len(iops), iops
    assert iops[3] / iops[2] < iops[1] / iops[0], iops


def test_edf_orders_within_priority_class():
    pipe = _timed_pipe(seed=1)
    _precondition(pipe, 64)
    svc = BlockDeviceService(pipe, max_inflight=1, policy="qos")
    svc.register("slack", QosClass("slack", priority=1, deadline_us=50_000.0))
    svc.register("tight", QosClass("tight", priority=1, deadline_us=100.0))
    blocker = svc.submit_read("slack", 0, at=0.0)
    # both arrive while the single slot is occupied; EDF must pick "tight"
    late = svc.submit_read("slack", 1, at=1.0)
    soon = svc.submit_read("tight", 2, at=2.0)
    svc.drain()
    assert blocker.t_dispatch < soon.t_dispatch < late.t_dispatch


def test_admission_rejects_past_queue_cap():
    pipe = _timed_pipe(seed=2)
    _precondition(pipe, 64)
    svc = BlockDeviceService(pipe, max_inflight=1)
    svc.register("t", QosClass("t", queue_cap=3))
    done = []
    for i in range(10):
        svc.submit_read("t", i, at=0.0, cb=done.append)
    svc.drain()
    ten = svc.tenants["t"]
    assert ten.rejected > 0 and ten.accepted + ten.rejected == 10
    assert ten.completed == ten.accepted
    statuses = {r.status for r in done}
    assert statuses == {DONE, REJECTED}
    # rejections complete through the CQ too, like an NVMe error completion
    assert svc.cq.pushed == 10
    # rejected requests never got device time and are excluded from stats
    assert svc.recorder.percentiles(op="R", tenant="t")["n"] == ten.accepted


def test_token_bucket_paces_dispatch():
    pipe = _timed_pipe(seed=3)
    _precondition(pipe, 64)
    svc = BlockDeviceService(pipe, max_inflight=64)
    svc.register("t", QosClass("t", rate_iops=10_000.0, burst=2))
    done = []
    for i in range(12):
        svc.submit_read("t", i, at=0.0, cb=done.append)
    svc.drain()
    assert len(done) == 12
    # burst of 2 up front, then one dispatch per 100us -- even with an idle
    # device the service must self-wake at refill instants
    disp = sorted(r.t_dispatch for r in done)
    assert disp[-1] - disp[0] >= 900.0
    assert svc.recorder.percentiles(op="R", tenant="t")["n"] == 12


# ----------------------------------------------------------- closed loop


def test_closed_loop_bounds_outstanding_window():
    pipe = _timed_pipe(seed=4)
    _precondition(pipe, 128)
    svc = BlockDeviceService(pipe, max_inflight=64)
    svc.register("t", QosClass("t"))
    reqs = synthetic(
        TenantSpec(name="t", kind="uniform", n_ops=50, read_frac=1.0,
                   arrival="closed", window=3, seed=6),
        128,
    )
    assert all(r.t_us == 0.0 for r in reqs)
    client = ClosedLoopClient(svc, "t", reqs, window=3)
    client.start(0.0)
    svc.drain()
    assert client.done() and client.completed == 50
    # no more than `window` requests ever overlap in [t_submit, t_done)
    spans = sorted((s.t_submit, s.t_done) for s in svc.recorder.samples)
    for t0, _ in spans:
        live = sum(1 for a, b in spans if a <= t0 < b)
        assert live <= 3


def test_multi_tenant_rejects_closed_loop_specs():
    with pytest.raises(ValueError, match="ClosedLoopClient"):
        multi_tenant([TenantSpec(name="c", arrival="closed")], 64)
    with pytest.raises(ValueError, match="arrival"):
        synthetic(TenantSpec(name="c", arrival="bogus"), 64)


# ------------------------------------------------- flush-tick interaction


def test_drained_submission_queue_still_flushes_partial_stripe():
    """Satellite fix: a lone service write (stripe never fills) must commit
    from ``engine.run()`` alone -- the timeout-flush tick re-arms itself
    while the service holds live work, with no drain() quiesce loop."""
    pipe = _timed_pipe()
    svc = BlockDeviceService(pipe, max_inflight=8)
    svc.register("t", QosClass("t"))
    done = []
    svc.submit_write("t", 5, np.ones((1, 256), np.uint8), at=0.0,
                     cb=done.append)
    pipe.engine.run()  # deliberately NOT svc.drain()
    assert len(done) == 1 and done[0].status == DONE
    assert pipe.array.stats.padded_blocks > 0
    # and the tick chain died with the work: the engine has quiesced
    assert pipe.engine.run() == 0


# ------------------------------------------------------------- stats


def test_per_tenant_stage_breakdown():
    pipe = _timed_pipe(seed=8)
    _precondition(pipe, 64)
    svc = BlockDeviceService(pipe, max_inflight=2)
    svc.register("a", LATENCY)
    svc.register("b", QosClass("b", priority=2))
    for i in range(20):
        svc.submit_read("a", i % 64, at=float(i))
        svc.submit_read("b", (i * 3) % 64, at=float(i))
    svc.drain()
    summ = svc.recorder.summary()
    for t in ("a", "b"):
        stages = summ["tenants"][t]["stage_means_us"]
        assert stages["queue_wait_us"] >= 0.0
        assert stages["service_us"] > 0.0
    # the background class queued strictly longer than the priority class
    a = summ["tenants"]["a"]["stage_means_us"]["queue_wait_us"]
    b = summ["tenants"]["b"]["stage_means_us"]["queue_wait_us"]
    assert b > a


# ------------------------------------------------------- async checkpoints


def _ckpt_service(seed=0):
    from repro.checkpoint.zapraid_ckpt import CheckpointConfig, CheckpointEngine

    cfg = CheckpointConfig(group_size=4, chunk_blocks=1, block_bytes=256,
                           zone_cap_blocks=256, n_zones=16)
    ckpt, pipe = CheckpointEngine.build_timed(
        cfg, 1024, seed=seed, flush_interval_us=200.0
    )
    svc = BlockDeviceService(pipe, max_inflight=16)
    svc.register("ckpt", QosClass("ckpt", priority=2))
    return ckpt, pipe, svc


def _state(seed):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal(128).astype(np.float32),
        "b": rng.standard_normal(64).astype(np.float32),
    }


def test_checkpoint_async_roundtrip_and_crash_ordering():
    ckpt, pipe, svc = _ckpt_service()
    s0, s1 = _state(1), _state(2)
    t0 = ckpt.save_async(0, s0, service=svc)
    svc.drain()
    t1 = ckpt.save_async(1, s1, service=svc)
    svc.drain()
    assert t0.done and t1.done and t1.t_done > t1.t_issue

    # crash ordering: the manifest write (at lba_base) was only submitted
    # after every leaf extent had acked
    reqs = svc.cq.drain()
    for ticket in (t0, t1):
        manifest = [r for r in reqs if r.op == "W" and r.lba == ckpt.lba_base
                    and abs(r.t_done - ticket.t_done) < 1e-9]
        assert len(manifest) == 1
        leaves = [r for r in reqs if r.op == "W" and r.lba != ckpt.lba_base
                  and r.t_submit <= manifest[0].t_submit]
        assert manifest[0].t_submit >= max(l.t_done for l in leaves)

    rt = ckpt.restore_async(1, s1, service=svc)
    svc.drain()
    assert rt.done and rt.n_extents == 2
    for k in s1:
        np.testing.assert_array_equal(np.asarray(rt.state[k]), s1[k])


def test_checkpoint_async_restore_degraded():
    ckpt, pipe, svc = _ckpt_service(seed=9)
    s0 = _state(3)
    ckpt.save_async(0, s0, service=svc)
    svc.drain()
    ckpt.fail_lane(1)
    rt = ckpt.restore_async(0, s0, service=svc)
    svc.drain()
    assert rt.done
    for k in s0:
        np.testing.assert_array_equal(np.asarray(rt.state[k]), s0[k])
    assert pipe.array.stats.degraded_reads > 0


def test_checkpoint_windows_share_one_array():
    from repro.checkpoint.zapraid_ckpt import (
        MANIFEST_LBAS,
        CheckpointConfig,
        CheckpointEngine,
    )

    cfg = CheckpointConfig(group_size=4, chunk_blocks=1, block_bytes=256,
                           zone_cap_blocks=256, n_zones=16)
    pipe = HandlerPipeline.build_timed(cfg.zap_cfg(1024), cfg.zns_cfg(),
                                       seed=0, flush_interval_us=200.0)
    svc = BlockDeviceService(pipe, max_inflight=16)
    span = MANIFEST_LBAS + 256
    engines, states, tickets = [], [], []
    for j in range(2):
        svc.register(f"job{j}", QosClass(f"job{j}", priority=2))
        engines.append(CheckpointEngine(cfg, 1024, array=pipe.array,
                                        lba_base=j * span, lba_span=span))
        states.append(_state(10 + j))
    for j, (eng, st) in enumerate(zip(engines, states)):
        tickets.append(eng.save_async(0, st, service=svc, tenant=f"job{j}"))
    svc.drain()
    assert all(t.done for t in tickets)
    # interleaved tenants, disjoint windows: each restores its own state
    for j, (eng, st) in enumerate(zip(engines, states)):
        rt = eng.restore_async(0, st, service=svc, tenant=f"job{j}")
        svc.drain()
        for k in st:
            np.testing.assert_array_equal(np.asarray(rt.state[k]), st[k])
