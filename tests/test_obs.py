"""Tests for the observability layer (``repro.obs``): trace-event JSON
schema and async-span nesting, metric registry/sampler monotonicity under
GC and rebuild, the load-bearing bit-identity of tracing-on vs tracing-off
runs across every RAID level (media, OOB, and L2P), the windowed-percentile
helper shared with the SLO monitor, the GC reserved-zone auto-size, and the
SLO monitor's dynamic-admission loop (shrink under pressure, restore once
the tail recovers, measurably better serving p99)."""
import math

import numpy as np
import pytest

from repro.core.array import ZapRaidConfig
from repro.core.handlers import HandlerPipeline
from repro.core.zns import ZnsConfig
from repro.obs import (
    Histogram,
    HostSpans,
    MetricsRegistry,
    MetricsSampler,
    Tracer,
    standard_collector,
    validate_metrics_series,
    validate_trace_events,
)
from repro.service import BlockDeviceService, ClosedLoopClient, QosClass
from repro.service.scenario import checkpoint_under_serving, read_qd_sweep
from repro.sim import TenantSpec, synthetic
from repro.sim.stats import LatencyRecorder

BB = 256
SCHEMES = ("raid4", "raid5", "raid6", "raid01")

SLO_KW = dict(window_us=1500.0, interval_us=250.0, min_samples=8)


def _timed_pipe(scheme="raid5", seed=0, logical_blocks=128, zones=8,
                zone_cap=64, **cfg_kw):
    n_drives = 5 if scheme == "raid6" else 4
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=4,
                        chunk_blocks=1, logical_blocks=logical_blocks,
                        gc_free_segments_low=1, **cfg_kw)
    zns = ZnsConfig(n_zones=zones, zone_cap_blocks=zone_cap, block_bytes=BB)
    return HandlerPipeline.build_timed(cfg, zns, seed=seed,
                                       flush_interval_us=200.0)


def _precondition(pipe, n_blocks, seed=1):
    rng = np.random.default_rng(seed)
    pipe.precondition(
        (lba, rng.integers(0, 256, (1, BB), dtype=np.uint8))
        for lba in range(n_blocks)
    )


def _workload(pipe, *, rounds=2, reads=48, fail=False, seed=5):
    """Deterministic timed write/read mix, optionally with a drive failure
    mid-stream and a paced rebuild -- reads after the failure sweep the
    whole LBA range so degraded decodes are guaranteed to occur."""
    logical = pipe.array.cfg.logical_blocks
    rng = np.random.default_rng(seed)
    t = 0.0
    for _ in range(rounds):
        for lba in range(0, logical - 2, 2):
            pipe.submit_write(
                lba, rng.integers(0, 256, (2, BB), dtype=np.uint8), at=t)
            t += 8.0
    for i in range(reads):
        pipe.submit_read((i * 5) % (logical - 3), 3, at=t)
        t += 10.0
    if fail:
        pipe.schedule_drive_failure(1, t + 50.0)
        for i in range(reads):
            pipe.submit_read((i * 7) % (logical - 2), 2,
                             at=t + 100.0 + 12.0 * i)
        pipe.schedule_rebuild(1, t + 100.0 + 14.0 * reads, interval_us=40.0)
    pipe.drain()


# ---------------------------------------------------------------- units


def test_histogram_buckets():
    h = Histogram()
    for v in (0.5, 1.0, 3.0, 1000.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["n"] == 4
    assert snap["total"] == pytest.approx(1004.5)
    assert snap["counts"][0] == 1          # < 1us
    assert sum(snap["counts"]) == 4


def test_registry_snapshot_and_clear():
    reg = MetricsRegistry()
    reg.inc("a", 2.0)
    reg.inc("a")
    reg.set("g", 7)
    reg.observe("h", 12.0)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["n"] == 1
    reg.clear()
    assert not reg.counters and not reg.gauges and not reg.histograms


def test_validate_metrics_series_catches_regressions():
    good = {"series": [
        {"t_us": 0.0, "counters": {"c": 1.0}, "gauges": {}},
        {"t_us": 5.0, "counters": {"c": 2.0}, "gauges": {"g": 1.0}},
    ]}
    validate_metrics_series(good)
    with pytest.raises(AssertionError, match="decreased"):
        validate_metrics_series({"series": [
            {"t_us": 0.0, "counters": {"c": 2.0}, "gauges": {}},
            {"t_us": 5.0, "counters": {"c": 1.0}, "gauges": {}},
        ]})
    with pytest.raises(AssertionError, match="monotone"):
        validate_metrics_series({"series": [
            {"t_us": 5.0, "counters": {}, "gauges": {}},
            {"t_us": 0.0, "counters": {}, "gauges": {}},
        ]})


def test_validate_trace_events_catches_mis_nesting():
    tr = Tracer()
    tr.req_begin(1, "io.request", 0.0)
    tr.req_begin(1, "sq.wait", 1.0)
    tr.req_end(1, "sq.wait", 2.0)
    tr.req_end(1, "io.request", 3.0)
    validate_trace_events(tr.to_trace_events())
    # unclosed span
    tr2 = Tracer()
    tr2.req_begin(1, "io.request", 0.0)
    with pytest.raises(AssertionError, match="unclosed"):
        validate_trace_events(tr2.to_trace_events())
    # crossed begin/end names
    tr3 = Tracer()
    tr3.req_begin(1, "a", 0.0)
    tr3.req_begin(1, "b", 1.0)
    tr3.req_end(1, "a", 2.0)
    tr3.req_end(1, "b", 3.0)
    with pytest.raises(AssertionError, match="mis-nested"):
        validate_trace_events(tr3.to_trace_events())


def test_tracer_lane_packing_separates_overlaps():
    tr = Tracer()
    tr.span("drive0", "read", 0.0, 10.0)
    tr.span("drive0", "read", 5.0, 15.0)   # overlaps -> second lane
    tr.span("drive0", "read", 12.0, 20.0)  # fits back in lane 0
    events = tr.to_trace_events()
    validate_trace_events(events)
    xs = [e for e in events if e["ph"] == "X"]
    tids = sorted(e["tid"] for e in xs)
    assert len(set(tids)) == 2             # two lanes, third span reuses one
    names = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert "drive0" in names.values() and "drive0 #1" in names.values()


def test_windowed_percentiles_and_empty_guard():
    rec = LatencyRecorder()
    for i in range(100):
        rec.record("t", "R", float(i), float(i) + 10.0 + i)
    full = rec.percentiles(op="R")
    assert full["n"] == 100
    win = rec.windowed_percentiles(0.0, 60.0, op="R", tenant="t")
    assert 0 < win["n"] < 100
    assert win["p99"] <= full["p99"]
    empty = rec.windowed_percentiles(1e6, 2e6, op="R")
    assert empty["n"] == 0
    assert math.isnan(empty["p99"]) and math.isnan(empty["mean"])
    # whole-run empty guard too (pre-obs this raised on np.percentile([]))
    assert LatencyRecorder().percentiles()["n"] == 0


# ------------------------------------------------------- trace from a run


def test_trace_schema_names_and_bounds():
    pipe = _timed_pipe(logical_blocks=96)
    _precondition(pipe, 96)
    tracer = pipe.attach_obs()
    _workload(pipe, rounds=2, fail=True)
    events = tracer.to_trace_events()
    validate_trace_events(events)
    assert tracer.dropped == 0
    names = {e["name"] for e in events}
    # device channel spans, background passes, degraded decode all present
    assert {"zone_append", "read"} <= names
    assert "degraded.decode" in names
    assert {"rebuild.full", "rebuild.segment"} & names
    # bookings may outlive the last processed event (drain-time flush), so
    # the bound is the device-time watermark, not the event clock
    t_end = max(pipe.engine.now, pipe.engine.io_watermark)
    for e in events:
        assert 0.0 <= e["ts"] <= t_end
        if e["ph"] == "X":
            assert e["ts"] + e["dur"] <= t_end + 1e-6


def test_request_spans_through_service():
    n_ops = 64
    pipe = _timed_pipe(logical_blocks=96)
    _precondition(pipe, 96)
    tracer = pipe.attach_obs()
    svc = BlockDeviceService(pipe, max_inflight=2, policy="qos")
    svc.tracer = tracer
    svc.register("t", QosClass("t"))
    reqs = synthetic(
        TenantSpec(name="t", kind="uniform", n_ops=n_ops, read_frac=0.5,
                   arrival="closed", window=8, seed=3),
        96,
    )
    client = ClosedLoopClient(svc, "t", reqs, window=8)
    client.start(0.0)
    svc.drain()
    assert client.done() and client.rejected == 0
    events = tracer.to_trace_events()
    validate_trace_events(events)
    begins = [e for e in events if e["ph"] == "b"]
    by_name = {}
    for e in begins:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["io.request"]) == n_ops
    assert len(by_name["device.service"]) == n_ops
    # window (2) < client QD (8) forces submission-queue waits
    assert by_name.get("sq.wait")
    dispatches = [e for e in events if e["ph"] == "n"
                  and e["name"] == "qos.dispatch"]
    assert dispatches and all("klass" in e["args"] for e in dispatches)
    # every io.request root carries tenant/op identity
    assert all(e["args"].get("tenant") == "t"
               for e in by_name["io.request"])


# ------------------------------------------- metrics under GC and rebuild


def test_metrics_monotone_under_gc_and_rebuild():
    pipe = _timed_pipe(logical_blocks=128, zones=6)
    _precondition(pipe, 128)
    reg = MetricsRegistry()
    sampler = MetricsSampler(pipe.engine, reg, standard_collector(pipe),
                             interval_us=25.0)
    sampler.start(0.0)
    pipe.schedule_gc(10.0, 100.0, n_ticks=50)
    _workload(pipe, rounds=6, fail=True)
    assert pipe.array.stats.gc_runs > 0          # pressure actually built
    assert len(sampler.series) > 10
    validate_metrics_series({"series": sampler.series})
    last = sampler.series[-1]
    assert last["counters"]["array/stripes_committed"] > 0
    assert "array/gc_reserved_zones" in last["gauges"]
    assert any(r["counters"].get("array/gc_blocks_moved", 0) > 0
               for r in sampler.series)
    # zone-state gauges cover every drive
    for d in pipe.array.drives:
        assert f"drive{d.drive_id}/zones_open" in last["gauges"]


def test_sampler_does_not_keep_engine_alive():
    pipe = _timed_pipe(logical_blocks=64)
    sampler = MetricsSampler(pipe.engine, MetricsRegistry(),
                             standard_collector(pipe), interval_us=10.0)
    sampler.start(0.0)
    pipe.drain()
    n = len(sampler.series)
    assert pipe.engine.pending() == 0            # no self-sustaining ticks
    pipe.drain()
    assert len(sampler.series) == n


# ------------------------------------------------------ bit-identity gate


@pytest.mark.parametrize(
    "scheme,host", [pytest.param(s, False, id=s) for s in SCHEMES]
    + [pytest.param(s, True, id=f"{s}-host_spans") for s in SCHEMES])
def test_tracing_is_observe_only(scheme, host):
    """Tracing+metrics on vs off (and, with ``host``, the wall-clock host
    span recorder on vs off): media, OOB, L2P, and the virtual clock must
    be bit-identical -- the obs layer may never book device time."""
    results = []
    for obs in (False, True):
        pipe = _timed_pipe(scheme=scheme, logical_blocks=96)
        _precondition(pipe, 96)
        rec = None
        if obs and host:
            rec = HostSpans().install()
        elif obs:
            pipe.attach_obs()
            sampler = MetricsSampler(
                pipe.engine, MetricsRegistry(), standard_collector(pipe),
                interval_us=20.0)
            sampler.start(0.0)
        try:
            _workload(pipe, rounds=2, fail=True)
        finally:
            if rec is not None:
                rec.uninstall()
        if rec is not None:   # the recorder saw the run it left unchanged
            spans = rec.snapshot()["spans"]
            assert spans["array:reconstruct"]["count"] > 0
            assert spans["service:loop"]["count"] > 0
        results.append(pipe)
    off, on = results
    assert off.engine.now == on.engine.now
    assert np.array_equal(off.array.l2p.flat, on.array.l2p.flat)
    for d0, d1 in zip(off.array.drives, on.array.drives):
        assert np.array_equal(d0.data, d1.data)
        assert np.array_equal(d0.oob, d1.oob)
        assert np.array_equal(d0.wp, d1.wp)
        assert np.array_equal(d0.state, d1.state)


def test_qd_sweep_rows_identical_with_obs():
    kw = dict(qds=(4,), n_ops=48, logical_blocks=1024, seed=0)
    assert read_qd_sweep(obs=False, **kw) == read_qd_sweep(obs=True, **kw)


# ------------------------------------------ media booking dispatch counter


def test_group_booking_counts_one_dispatch_per_group(monkeypatch):
    """With host spans on, the planner's ``media:book`` span is a dispatch
    keyed ``plan_group_appends`` with shapes ``((G*n,),)``: one per group
    committed, carrying its G*n commands; in a healthy write workload no
    append is booked outside a group."""
    from repro.core.array import ZapRAIDArray
    pipe = _timed_pipe(logical_blocks=96)
    _precondition(pipe, 96)
    commands = []
    commit = ZapRAIDArray._commit_built_group

    def counted(self, grp):
        commands.append(len(grp["seqs"]) * grp["ost"].info.n_drives)
        return commit(self, grp)

    monkeypatch.setattr(ZapRAIDArray, "_commit_built_group", counted)
    rec = HostSpans(annotate=False).install()
    try:
        _workload(pipe, rounds=2)
    finally:
        rec.uninstall()
    dispatches = rec.snapshot()["dispatches"]
    plans = {shapes: k for (op, shapes), k in dispatches.items()
             if op == "plan_group_appends"}
    assert sum(plans.values()) == len(commands) > 0
    assert sum(k * shapes[0][0] for shapes, k in plans.items()) == sum(commands)
    assert not [op for op, _ in dispatches if op == "book_append"]


def test_unplanned_appends_count_as_book_append():
    """An append with no planned time books itself under the ``book_append``
    key, one shape entry per call with its command count; planned commits
    add none."""
    from repro.core.zns import OOB_DTYPE
    from repro.sim import Engine, ServiceModel, TimedDrive
    from repro.sim.device import plan_group_appends
    eng = Engine()
    d = TimedDrive(ZnsConfig(n_zones=2, zone_cap_blocks=64, block_bytes=BB), 0,
                   engine=eng, service=ServiceModel(block_bytes=BB))
    chunks = np.zeros((3, 1, BB), np.uint8)
    oobs = np.zeros((3, 1), OOB_DTYPE)
    rec = HostSpans(annotate=False).install()
    try:
        d.zone_append_commit(0, chunks[0], oobs[0])
        d.zone_append_commit_many(0, chunks, oobs)
        plan_group_appends([d], (0,), [(s, 0) for s in range(3)], 1, eng.now)
        d.zone_append_commit_many(0, chunks, oobs)
    finally:
        rec.uninstall()
    snap = rec.snapshot()
    booked = {key: k for key, k in snap["dispatches"].items()
              if not key[0].startswith("crc32c")}
    assert booked == {("book_append", ((1,),)): 1,
                                  ("book_append", ((3,),)): 1,
                                  ("plan_group_appends", ((3,),)): 1}
    assert snap["spans"]["media:book"]["count"] == 4
    assert len(d.chunk_done) == 7


# ------------------------------------------------------ escrow auto-size


def test_gc_escrow_auto_sizes_from_geometry():
    pipe = _timed_pipe(logical_blocks=96, zones=16)
    arr = pipe.array
    auto = len(arr.cfg.chunk_sizes())
    assert arr.reserved_zones() == 0             # roomy array: no escrow
    base_free = arr.free_segment_count()
    # drain free zones until the array is near-full -> escrow kicks in
    while min(len(fz) for fz in arr.free_zones) > \
            auto + arr.cfg.gc_free_segments_low + 1:
        for fz in arr.free_zones:
            fz.pop()
    assert arr.reserved_zones() == auto
    assert arr.free_segment_count() < base_free
    # an explicit setting always wins, roomy or not
    pipe2 = _timed_pipe(logical_blocks=96, zones=16, gc_reserved_zones=2)
    assert pipe2.array.reserved_zones() == 2


# ------------------------------------------------------------ SLO monitor


def test_slo_monitor_shrinks_and_restores():
    res = checkpoint_under_serving(
        policy="qos", seed=0, restore_check=False,
        slo_objective_us=200.0, slo_kwargs=dict(SLO_KW),
        sampler_interval_us=100.0,
    )
    s = res["slo"]
    assert s["n_shrinks"] > 0, s
    assert s["n_restores"] > 0, s
    assert 1 <= s["min_cap"] < s["default_cap"]
    assert s["final_cap"] <= s["default_cap"]
    assert res["slo_actions"]
    # the sampler saw the actuated cap move below the default
    caps = [r["gauges"].get("class/ckpt/cap") for r in res["metrics_series"]]
    assert any(c is not None and c < s["default_cap"] for c in caps)
    validate_metrics_series({"series": res["metrics_series"]})


def test_slo_monitor_recovers_serving_p99():
    static = checkpoint_under_serving(policy="qos", seed=0,
                                      restore_check=False)
    dyn = checkpoint_under_serving(
        policy="qos", seed=0, restore_check=False,
        slo_objective_us=150.0, slo_kwargs=dict(SLO_KW),
    )
    assert static["serve_p99_us"] > 150.0        # pressure exists to relieve
    assert dyn["serve_p99_us"] < static["serve_p99_us"]
    assert dyn["slo"]["n_shrinks"] > 0
    # checkpoint traffic still completes, just slower
    assert dyn["ckpt_save_max_us"] >= static["ckpt_save_max_us"]
