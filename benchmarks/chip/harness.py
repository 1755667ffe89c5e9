"""One run of one cell: build, prefill, fail drives, warm up, measure, check.

``run_cell`` does the work and returns the result object that ``run.py``
prints; it takes the cell as data (``specs.Cell``), so the tests drive it
at a small geometry on the CPU.  In order:

1. build the array with ``HandlerPipeline.build_timed`` and a
   ``BlockDeviceService`` in front of it;
2. prefill the volume through ``precondition`` with bytes drawn from the
   seed, recorded in the plain reference as they are handed over;
3. fail the traffic's drives;
4. warm up the codec shapes this cell's traffic dispatches and no others;
5. run the closed loop for ``seconds`` of wall time (with ``trace``: the
   layer spans and the profiler on), drain, read the device's memory peak;
6. check against the reference: every read the window answered; for write
   cells, at a seeded eighth of the acknowledgements, that the write is on
   the media already, then after the drain a seeded sample of the volume
   read back healthy and again with as many drives failed as the
   configuration survives, and the checksums stored beside the blocks.

Every time is host wall time (``time.perf_counter``) or device time from
the profiler trace; the drive model's virtual clock only orders events.
"""
from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

import layers
import tracereduce
from loadgen import AddressStream, ClosedLoop, PayloadSource
from reference import BlockReference, crc32c_rows
from specs import HERE, Cell

MiB = 1 << 20
TENANT = "bench"
PREFILL_CHUNK_BLOCKS = 1024
WARM_READS = 512
ACK_EVERY = 8


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class HarnessError(RuntimeError):
    """The cell cannot be run as specified."""


def _sub_seeds(seed: int) -> dict:
    """Independent generators for each use of the seed."""
    names = ("prefill", "traffic", "payload", "drives", "check")
    kids = np.random.SeedSequence(abs(int(seed))).spawn(len(names))
    return {n: np.random.default_rng(k) for n, k in zip(names, kids)}


def zones_per_drive(config: dict, k: int, volume_blocks: int) -> int:
    from repro.core.segment import solve_stripes_per_segment
    stripes, _ = solve_stripes_per_segment(
        config["zone_cap_blocks"], config["chunk_blocks"], config["block_bytes"])
    return math.ceil(1.5 * volume_blocks / (k * stripes)) + 2


@dataclasses.dataclass
class Built:
    pipe: object
    svc: object
    arr: object
    volume_blocks: int
    n_zones: int


def build(config: dict, traffic: dict, drive_seed: int) -> Built:
    from repro.core.array import ZapRaidConfig
    from repro.core.handlers import HandlerPipeline
    from repro.core.raid import make_scheme
    from repro.core.zns import ZnsConfig
    from repro.service import BlockDeviceService, QosClass

    bb = config["block_bytes"]
    volume_blocks = traffic["volume_mib"] * MiB // bb
    k = make_scheme(config["scheme"], config["n_drives"]).k
    n_zones = zones_per_drive(config, k, volume_blocks)
    cfg = ZapRaidConfig(
        scheme=config["scheme"], n_drives=config["n_drives"],
        group_size=config["group_size"], chunk_blocks=config["chunk_blocks"],
        logical_blocks=volume_blocks,
        gc_free_segments_low=config["gc_free_segments_low"],
        batched=config["batched"], verify_reads=config["verify_reads"],
        append_order=config["append_order"],
    )
    zns = ZnsConfig(n_zones=n_zones, zone_cap_blocks=config["zone_cap_blocks"],
                    block_bytes=bb, max_open_zones=config["max_open_zones"])
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=drive_seed)
    svc = BlockDeviceService(pipe, max_inflight=traffic["qd"], policy="fifo")
    svc.register(TENANT, QosClass(TENANT, queue_cap=1 << 30))
    return Built(pipe, svc, pipe.array, volume_blocks, n_zones)


def codec_modes(arr) -> list[tuple[bool, bool]]:
    """``(use_pallas, interpret)`` of every codec the array holds."""
    return [(c.use_pallas, c.interpret) for c in arr._codecs.values()]


def prefill(b: Built, ref: BlockReference, traffic: dict, rng) -> None:
    bb = b.arr.zns_cfg.block_bytes
    n = min(b.volume_blocks, traffic["prefill_mib"] * MiB // bb)
    data = np.frombuffer(bytearray(rng.bytes(n * bb)), np.uint8).reshape(n, bb)
    ref.write(0, data)
    b.pipe.precondition(
        (lba, data[lba:lba + PREFILL_CHUNK_BLOCKS])
        for lba in range(0, n, PREFILL_CHUNK_BLOCKS)
    )


def warm_up(b: Built, traffic: dict, rng) -> None:
    """Compile, or load from the cache, what the window will dispatch.

    Writes: the group encode of data and of metadata at every power-of-two
    stripe count up to G (a flush or a segment's end commits a partial
    group).  Reads: degraded reads of seeded LBAs through the array, which
    reach every parity rotation, so every survivor set's decode."""
    arr = b.arr
    if traffic["op"] == "write":
        codec = arr.codec
        k, c = codec.scheme.k, arr.cfg.chunk_blocks
        lanes = c * arr.zns_cfg.block_bytes // 4
        meta_lanes = 16 * c // 4   # a (lba, ts) u64 pair per block
        s = 1
        while s <= arr.cfg.group_size:
            for n in (lanes, meta_lanes):
                codec.materialize(codec.encode_batch_async(
                    np.zeros((s, k, n), np.int32)))
            s *= 2
    else:
        for lba in rng.integers(0, b.volume_blocks, WARM_READS):
            arr.read(int(lba), 1)
        for d in arr.drives:
            d.reset_timing()   # the warm-up's reads book no device time


@dataclasses.dataclass
class Window:
    """What a run measured, for the metric readers (``metrics/*.py``)."""

    cell: Cell
    setup_s: float
    window_s: float
    loop: ClosedLoop
    block_bytes: int
    stats0: dict
    stats1: dict
    spans: Optional[dict] = None       # layers.LayerSpans.snapshot()
    trace: Optional[dict] = None       # device events and window bounds
    peaks: Optional[dict] = None

    def mib(self, op: str) -> float:
        """User MiB acknowledged (writes) or returned (reads) by the
        deadline."""
        return sum(s.n_blocks for s in self.loop.in_window(op)) \
            * self.block_bytes / MiB

    def latencies_ms(self, op: str) -> np.ndarray:
        return np.sort([(s.t_done - s.t_submit) * 1e3
                        for s in self.loop.in_window(op)])

    def percentile_ms(self, op: str, q: float) -> Optional[float]:
        """Nearest-rank percentile over every request of ``op`` completed
        in the window."""
        lat = self.latencies_ms(op)
        if lat.size == 0:
            return None
        return float(lat[max(0, math.ceil(q / 100 * lat.size) - 1)])

    def stat(self, name: str) -> int:
        return self.stats1[name] - self.stats0[name]

    # -- traced run ---------------------------------------------------------

    def layer_s(self, layer: str) -> Optional[float]:
        if self.spans is None:
            return None
        return self.spans["self_s"].get(layer, 0.0)

    def service_s(self) -> Optional[float]:
        """Window time outside every wrapped layer: the service, the
        handler pipeline and the event loop."""
        if self.spans is None:
            return None
        return self.window_s - sum(self.spans["self_s"].values())

    def per_mib_ms(self, seconds: Optional[float], op: str) -> Optional[float]:
        mib = self.mib(op)
        if seconds is None or mib <= 0:
            return None
        return seconds * 1e3 / mib

    def device_events(self) -> Optional[list]:
        if not self.trace or not self.trace["device"]:
            return None
        return [e for evs in self.trace["device"].values() for e in evs]

    def idle_pct(self) -> Optional[float]:
        busy = self.busy_s()
        if busy is None:
            return None
        return 100.0 * (1.0 - busy / self.trace["window_s"])

    def busy_s(self) -> Optional[float]:
        """Device-busy seconds in the traced window, averaged over chips."""
        if not self.trace or not self.trace["device"]:
            return None
        t0, t1 = self.trace["t0_ns"], self.trace["t1_ns"]
        per_chip = [tracereduce.busy_ns(evs, t0, t1)
                    for evs in self.trace["device"].values()]
        busy = sum(per_chip) / len(per_chip) * 1e-9
        return busy if busy > 0 else None

    def roofline_pct(self, family: str, programs) -> Optional[float]:
        """Share of the HBM roofline: the bytes the programs' dispatches
        must move, at the chip's peak bandwidth, over their device time."""
        events = self.device_events()
        if events is None or self.spans is None or self.peaks is None:
            return None
        t0, t1 = self.trace["t0_ns"], self.trace["t1_ns"]
        events = [e for e in events if t0 <= e[2] <= t1]
        ns = tracereduce.module_ns(events, programs)
        sizes = self.cell.kernels[family].BYTES
        moved = sum(n * sizes[op](shapes)
                    for (op, shapes), n in self.spans["dispatches"].items()
                    if op in programs)
        if ns <= 0 or moved <= 0:
            return None
        return 100.0 * moved / self.peaks["hbm_bytes_per_s"] / (ns * 1e-9)


def _stats(arr) -> dict:
    return dataclasses.asdict(arr.stats)


def locate(arr, lbas: np.ndarray):
    """Where the array's L2P puts each LBA's block: ``(mapped, drive, zone,
    offset)``, ``mapped`` False where it puts none."""
    from repro.core.l2p import NO_PBA, unpack_pba_many
    pbas = arr.l2p.get_many(np.asarray(lbas, np.int64))
    mapped = pbas != int(NO_PBA)
    segs, members, offs = unpack_pba_many(np.where(mapped, pbas, 0))
    drives = np.zeros(pbas.shape, np.int64)
    zones = np.zeros(pbas.shape, np.int64)
    for s, m in set(zip(segs[mapped].tolist(), members[mapped].tolist())):
        info = arr.segments[s].info
        sel = mapped & (segs == s) & (members == m)
        drives[sel] = info.drive_ids[m]
        zones[sel] = info.zone_ids[m]
    return mapped, drives, zones, offs


def media(arr, drives, zones, offs, where=None):
    """The bytes and the stored CRC32C at each ``(drive, zone, offset)``,
    read straight from the drives (zeros where ``where`` is False)."""
    where = np.ones(drives.shape, bool) if where is None else where
    blocks = np.zeros((drives.size, arr.zns_cfg.block_bytes), np.uint8)
    crcs = np.zeros(drives.size, np.uint32)
    for d in np.unique(drives[where]):
        sel = where & (drives == d)
        blocks[sel] = arr.drives[d].data[zones[sel], offs[sel]]
        crcs[sel] = arr.drives[d].crc[zones[sel], offs[sel]]
    return blocks, crcs


def unpersisted_blocks(arr, writes: list, payloads: PayloadSource,
                       k: int) -> int:
    """Blocks of the ``k``-th write that are not on the media.  Each must
    read, where the L2P puts it, as its own payload or as a later payload
    to the same LBA (``writes[i]`` carries payload ``i``)."""
    n = writes[k].n_blocks
    lbas = writes[k].lba + np.arange(n)
    mapped, drives, zones, offs = locate(arr, lbas)
    got, _ = media(arr, drives, zones, offs, mapped)
    gi, gj = payloads.stamps(got)
    ok = mapped & (gi >= k) & (gi < len(writes)) & (gj >= 0) & (gj < n)
    idx = np.flatnonzero(ok)
    start = np.array([writes[g].lba for g in gi[idx]], np.int64)
    ok[idx] = (start + gj[idx] == lbas[idx]) & np.all(
        got[idx] == payloads.blocks(gi[idx], gj[idx], n), axis=1)
    return int((~ok).sum())


def crc_mismatches(arr, ref: BlockReference, lbas: np.ndarray, rng,
                   n_sample: int) -> int:
    """Blocks whose stored CRC32C is wrong: each sampled LBA's block
    against the reference's bytes, and a seeded sample of every block on
    the media (parity, headers and footers too) against its bytes."""
    mapped, drives, zones, offs = locate(arr, lbas)
    _, stored = media(arr, drives, zones, offs, mapped)
    bad = int((~mapped).sum())
    bad += int((stored[mapped] != crc32c_rows(ref.blocks[lbas[mapped]])).sum())
    written = [(d, z, int(drv.wp[z])) for d, drv in enumerate(arr.drives)
               for z in np.flatnonzero(drv.wp)]
    counts = np.array([w for _, _, w in written], np.int64)
    ends = np.cumsum(counts)
    pick = np.sort(rng.integers(0, ends[-1], n_sample))
    k = np.searchsorted(ends, pick, side="right")
    drives = np.array([written[x][0] for x in k], np.int64)
    zones = np.array([written[x][1] for x in k], np.int64)
    offs = pick - (ends[k] - counts[k])
    blocks, stored = media(arr, drives, zones, offs)
    return bad + int((stored != crc32c_rows(blocks)).sum())


def read_back(b: Built, ref: BlockReference, lbas: np.ndarray, n_blocks: int):
    """Read ``n_blocks`` at each LBA through the array; mismatched blocks."""
    bad = 0
    for lba in lbas:
        got = b.arr.read(int(lba), n_blocks)
        bad += ref.mismatches(int(lba), n_blocks, got)
    return bad


def check(b: Built, ref: BlockReference, loop: ClosedLoop, cell: Cell,
          rng, stats_window: dict, tallies: dict) -> dict:
    """Every number compared, each with its limit and rule."""
    traffic, config = cell.traffic, cell.config
    checks = {}
    failed = sum(1 for s in loop.samples if not s.ok)
    checks["failed_requests"] = (failed, 0, "<=")
    checks["compiles_in_window"] = (tallies["compiles"], 0, "<=")
    if traffic["op"] == "read":
        bad = sum(ref.mismatches(s.lba, s.n_blocks, s.result)
                  for s in loop.samples if s.ok)
        checks["read_mismatched_blocks"] = (bad, 0, "<=")
        checks["window_degraded_reads"] = (stats_window["degraded_reads"], 1, ">=")
        return checks
    # writes: a seeded sample of the extents the window acknowledged, read
    # back healthy, then with the configuration's drive losses
    n = traffic["request_blocks"]
    acked = np.unique([s.lba for s in loop.samples if s.ok])
    want = max(1, traffic["check_mib"] * MiB // (n * config["block_bytes"]))
    lbas = rng.choice(acked, size=min(want, acked.size), replace=False)
    checks["unpersisted_acked_blocks"] = (tallies["unpersisted"], 0, "<=")
    checks["readback_mismatched_blocks"] = (read_back(b, ref, lbas, n), 0, "<=")
    blocks = (lbas[:, None] + np.arange(n)).ravel()
    checks["crc_mismatched_blocks"] = (
        crc_mismatches(b.arr, ref, blocks, rng, blocks.size), 0, "<=")
    losses = config["guarantee"]["drive_losses_survived"]
    failed_drives = sorted(rng.choice(config["n_drives"], losses, replace=False))
    d0 = b.arr.stats.degraded_reads
    for d in failed_drives:
        b.arr.fail_drive(int(d))
    checks["degraded_mismatched_blocks"] = (read_back(b, ref, lbas, n), 0, "<=")
    checks["degraded_blocks_decoded"] = (b.arr.stats.degraded_reads - d0, 1, ">=")
    return checks


def passes(value, limit, rule) -> bool:
    return value <= limit if rule == "<=" else value >= limit


def _device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True) -> dict:
    """Run one cell; returns the result object (the contract's last line)."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    peaks = load_peaks(devs[0].device_kind) if require_tpu else None
    clock = layers.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    rngs = _sub_seeds(seed)
    config, traffic = cell.config, cell.traffic
    bb = config["block_bytes"]

    b = build(config, traffic, int(rngs["drives"].integers(1 << 31)))
    if require_tpu and any(m != (True, False) for m in codec_modes(b.arr)):
        raise HarnessError(f"codec resolved to {codec_modes(b.arr)}, not the "
                           "compiled Pallas kernels (use_pallas=True, "
                           "interpret=False)")
    log(f"[{cell.name}] {config['scheme']} {config['n_drives']} drives x "
        f"{b.n_zones} zones of {config['zone_cap_blocks']} blocks; volume "
        f"{b.volume_blocks} blocks; codec {codec_modes(b.arr)}")
    ref = BlockReference(b.volume_blocks, bb)
    t = time.perf_counter()
    prefill(b, ref, traffic, rngs["prefill"])
    log(f"[{cell.name}] prefill {traffic['prefill_mib']} MiB: host wall "
        f"{time.perf_counter() - t:.3f} s")
    for d in traffic["failed_drives"]:
        b.arr.fail_drive(int(d))
    t = time.perf_counter()
    warm_up(b, traffic, rngs["traffic"])
    log(f"[{cell.name}] warm-up: host wall {time.perf_counter() - t:.3f} s")

    stream = AddressStream(traffic["address"], b.volume_blocks,
                           traffic["request_blocks"], rngs["traffic"])
    payloads = PayloadSource(bb, rngs["payload"]) if traffic["op"] == "write" else None
    loop = ClosedLoop(b.svc, TENANT, traffic["op"], traffic["request_blocks"],
                      traffic["qd"], stream, ref, payloads)
    spans = window_span = None
    trace_dir = None
    if trace:
        spans = layers.LayerSpans().install(extra=(
            ("client", PayloadSource, ("make",)),
            ("client", BlockReference, ("write",)),
            ("client", sys.modules[__name__], ("unpersisted_blocks",)),
        ))
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the window as the trace clocks it: device time is read inside it
        window_span = jax.profiler.TraceAnnotation("bench:window")
    captured = {}

    def on_close():
        captured["stats1"] = _stats(b.arr)
        captured["compiles"] = clock.count
        if spans is not None:
            spans.active = False
            captured["spans"] = spans.snapshot()
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    loop.on_close = on_close
    unpersisted = [0]
    if payloads is not None:
        # a write is acknowledged only once it has persisted: at every
        # ACK_EVERY-th acknowledgement, from a seeded phase, its blocks must
        # be on the media already
        phase = int(rngs["check"].integers(ACK_EVERY))

        def on_ack(k: int) -> None:
            if k % ACK_EVERY == phase:
                unpersisted[0] += unpersisted_blocks(b.arr, loop.samples,
                                                     payloads, k)

        loop.on_ack = on_ack
    stats0 = _stats(b.arr)
    compiles0, compile_s0 = clock.count, clock.seconds
    setup_s = time.perf_counter() - t_process
    if spans is not None:
        window_span.__enter__()
        spans.active = True
    loop.run(seconds)
    window = Window(cell=cell, setup_s=setup_s, window_s=loop.t_close - loop.t_start,
                    loop=loop, block_bytes=bb, stats0=stats0,
                    stats1=captured["stats1"], spans=captured.get("spans"),
                    peaks=peaks)
    if spans is not None:
        spans.uninstall()
        window.trace = _read_trace(trace_dir, window.window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = _device_info(jax)
    tallies = {"compiles": captured["compiles"] - compiles0,
                "unpersisted": unpersisted[0]}
    log(f"[{cell.name}] set-up {setup_s:.3f} s, of which compile "
        f"{compile_s0:.3f} s over {compiles0} compiles; compiles in the "
        f"window: {tallies['compiles']}")
    log(f"[{cell.name}] window {window.window_s:.3f} s: {len(loop.samples)} "
        f"requests issued, {len(loop.in_window(traffic['op']))} completed by "
        f"the deadline")

    result = {"correct": False, "attempted": len(loop.samples),
              "failed": sum(1 for s in loop.samples if not s.ok),
              "metrics": {}, "device": device}
    for name, mod, entry in cell.metrics:
        value = mod.read(window)
        if value is not None:
            result["metrics"][name] = {"value": float(value), "unit": entry["unit"]}
    if trace:
        device["busy_s"] = window.busy_s() or 0.0
        device["window_s"] = window.trace["window_s"] if window.trace else window.window_s
        if window.trace and window.trace["breakdown"]:
            result["breakdown"] = window.trace["breakdown"]

    stats_window = {k: window.stats1[k] - window.stats0[k] for k in stats0}
    t = time.perf_counter()
    checks = check(b, ref, loop, cell, rngs["check"], stats_window, tallies)
    log(f"[{cell.name}] check: host wall {time.perf_counter() - t:.3f} s")
    if require_tpu:
        bad = sum(m != (True, False) for m in codec_modes(b.arr))
        checks["codecs_not_compiled"] = (bad, 0, "<=")
    result["correct"] = all(passes(*c) for c in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim, "rule": rule}
                        for k, (v, lim, rule) in checks.items()}
    for k, (v, lim, rule) in checks.items():
        log(f"check {k}: {v} (limit {rule} {lim})")
    return result


def _read_trace(trace_dir: str, window_s: float) -> Optional[dict]:
    from jax.profiler import ProfileData
    try:
        path = tracereduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    device, host = tracereduce.events_from_profile(ProfileData.from_file(str(path)))
    marks = [(s, d) for name, s, d in host if name == "bench:window"]
    if marks:
        t0, dur = marks[0]
        t1 = t0 + dur
    else:  # no window span: the whole trace
        all_ev = [e for evs in device.values() for e in evs]
        t0 = min((e[2] for e in all_ev), default=0.0)
        t1 = t0 + window_s * 1e9
    host = [h for h in host if h[0] != "bench:window"]
    breakdown = None
    if device:
        evs = [e for v in device.values() for e in v if t0 <= e[2] <= t1]
        gaps = tracereduce.idle_gaps(next(iter(device.values())), t0, t1)
        breakdown = {"device_ops": tracereduce.top_ops(evs),
                     "idle_gaps": tracereduce.attribute_gaps(gaps, host)}
    return {"device": device, "t0_ns": t0, "t1_ns": t1,
            "window_s": (t1 - t0) * 1e-9, "breakdown": breakdown}
