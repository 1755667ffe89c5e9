"""One run of one cell: the frame that every kind of run shares.

``run_cell`` does the work and returns the result object that ``run.py``
prints; it takes the cell as data (``specs.Cell``), so the tests drive it
at a small geometry on the CPU.  In order:

1. look up the device and its peaks; build the array with
   ``HandlerPipeline.build_timed`` from the configuration's keys;
2. prefill the volume through ``precondition`` with bytes drawn from the
   seed, recorded in the plain reference as they are handed over;
3. fail the traffic's ``failed_drives``;
4. the runner's ``prepare``: warm up what its window dispatches and no
   more, and set up the window's work;
5. run the window for ``seconds`` of wall time (with ``trace``: the
   benchmark's wrappers, the program's span recorder and the profiler on),
   read the device's memory peak and the metrics;
6. check: the frame's own numbers (no unit of work failed, no compile in
   the window, every codec compiled), then the runner's, against the
   reference.

A runner (``runners/<name>.py``, named by the traffic's ``runner``) is one
kind of run.  It provides

* ``CLIENT``: ``(layer, owner, attrs)`` points of its own client code that
  the traced window wraps (``layers.LayerSpans.install``), so that the
  service's time leaves them out;
* ``prepare(b, ref, cell, rngs)``: the warm-up, then the window's work: an
  object with ``run(seconds)``, which measures and calls ``on_close`` (set
  by the frame) once, as the window closes; ``samples``, one
  ``loadgen.Sample`` per unit of work (``attempted`` and ``failed`` count
  them); ``t_start`` and ``t_close``, the window's bounds; and
  ``in_window(op)``, the samples of the traffic's ``op`` that the metric
  readers count (``Window.mib``);
* ``check(b, ref, work, cell, rng, stats_window)``: its numbers compared,
  ``{name: (value, limit, rule)}``.

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
from reference import BlockReference, crc32c_rows
from specs import HERE, Cell, config_parts

MiB = 1 << 20
PREFILL_CHUNK_BLOCKS = 1024


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class HarnessError(RuntimeError):
    """The cell cannot be run as specified."""


def _sub_seeds(seed: int) -> dict:
    """Independent generators for each use of the seed."""
    names = ("prefill", "traffic", "payload", "drives", "check")
    kids = np.random.SeedSequence(abs(int(seed))).spawn(len(names))
    return {n: np.random.default_rng(k) for n, k in zip(names, kids)}


def zones_per_drive(cfg, zns, k: int, volume_blocks: int) -> int:
    """The volume and half again for GC headroom, in segments of
    ``chunk_blocks`` chunks, then two zones for each segment open at once
    (one; ``n_small + n_large`` with hybrid data management): its own, and
    the GC watermark's, which also takes the segment a drive failure opens
    in its place at survivor width."""
    from repro.core.segment import solve_stripes_per_segment
    stripes, _ = solve_stripes_per_segment(
        zns.zone_cap_blocks, cfg.chunk_blocks, zns.block_bytes)
    open_segments = cfg.n_small + cfg.n_large if cfg.hybrid else 1
    return math.ceil(1.5 * volume_blocks / (k * stripes)) + 2 * open_segments


def array_configs(config: dict, traffic: dict):
    """``(ZapRaidConfig, ZnsConfig)`` of a cell: the configuration's keys as
    they stand, the volume from the traffic, the zones by
    :func:`zones_per_drive`."""
    from repro.core.array import ZapRaidConfig
    from repro.core.raid import make_scheme
    from repro.core.zns import ZnsConfig

    array_keys, zns_keys = config_parts(config)
    zns = ZnsConfig(**zns_keys)
    volume_blocks = traffic["volume_mib"] * MiB // zns.block_bytes
    cfg = ZapRaidConfig(logical_blocks=volume_blocks, **array_keys)
    k = make_scheme(cfg.scheme, cfg.n_drives).k
    zns.n_zones = zones_per_drive(cfg, zns, k, volume_blocks)
    return cfg, zns


@dataclasses.dataclass
class Built:
    pipe: object
    arr: object
    volume_blocks: int
    n_zones: int


def build(config: dict, traffic: dict, drive_seed: int) -> Built:
    from repro.core.handlers import HandlerPipeline

    cfg, zns = array_configs(config, traffic)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=drive_seed)
    return Built(pipe, pipe.array, cfg.logical_blocks, zns.n_zones)


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


@dataclasses.dataclass
class Window:
    """What a run measured, for the metric readers (``metrics/*.py``)."""

    cell: Cell
    setup_s: float
    window_s: float
    loop: object                       # the runner's work (see the module doc)
    block_bytes: int
    stats0: dict
    stats1: dict
    spans: Optional[dict] = None       # layers.LayerSpans.snapshot()
    program: Optional[dict] = None     # repro.obs.HostSpans.snapshot()
    trace: Optional[dict] = None       # device events and window bounds
    peaks: Optional[dict] = None

    def mib(self, op: str) -> float:
        """MiB of the samples of ``op`` the window counts: user MiB
        acknowledged (writes) or returned (reads) by the deadline, MiB
        restored (rebuild passes)."""
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

    # -- the program's own spans (repro.obs.hostspans) -----------------------

    def program_self_s(self, *names: str,
                       layer: Optional[str] = None) -> Optional[float]:
        """Summed self seconds of the named program spans, or of every span
        of ``layer``; 0.0 for spans that never opened."""
        if self.program is None:
            return None
        return sum(s["self_s"] for name, s in self.program["spans"].items()
                   if name in names or name.split(":")[0] == layer)

    def program_count(self, name: str) -> Optional[int]:
        if self.program is None:
            return None
        return self.program["spans"].get(name, {}).get("count", 0)

    def program_per_mib_ms(self, op: str, *names: str,
                           layer: Optional[str] = None) -> Optional[float]:
        """Self milliseconds of the program spans per MiB of ``op``."""
        return self.per_mib_ms(self.program_self_s(*names, layer=layer), op)

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
    from repro.obs.hostspans import HostSpans

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    peaks = load_peaks(devs[0].device_kind) if require_tpu else None
    clock = layers.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    rngs = _sub_seeds(seed)
    traffic, runner = cell.traffic, cell.runner

    b = build(cell.config, traffic, int(rngs["drives"].integers(1 << 31)))
    cfg, zns = b.arr.cfg, b.arr.zns_cfg
    bb = zns.block_bytes
    if require_tpu and any(m != (True, False) for m in codec_modes(b.arr)):
        raise HarnessError(f"codec resolved to {codec_modes(b.arr)}, not the "
                           "compiled Pallas kernels (use_pallas=True, "
                           "interpret=False)")
    log(f"[{cell.name}] {cfg.scheme} {cfg.n_drives} drives x "
        f"{b.n_zones} zones of {zns.zone_cap_blocks} blocks; volume "
        f"{b.volume_blocks} blocks; codec {codec_modes(b.arr)}")
    ref = BlockReference(b.volume_blocks, bb)
    t = time.perf_counter()
    prefill(b, ref, traffic, rngs["prefill"])
    log(f"[{cell.name}] prefill {traffic['prefill_mib']} MiB: host wall "
        f"{time.perf_counter() - t:.3f} s")
    for d in traffic.get("failed_drives", ()):
        b.arr.fail_drive(int(d))
    t = time.perf_counter()
    work = runner.prepare(b, ref, cell, rngs)
    log(f"[{cell.name}] warm-up: host wall {time.perf_counter() - t:.3f} s")

    spans = program = window_span = None
    trace_dir = None
    if trace:
        spans = layers.LayerSpans().install(extra=runner.CLIENT)
        program = HostSpans().install()
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
            # spans still open (the event loop's root among them) count up
            # to this moment
            captured["program"] = program.snapshot()
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    work.on_close = on_close
    stats0 = _stats(b.arr)
    compiles0, compile_s0 = clock.count, clock.seconds
    setup_s = time.perf_counter() - t_process
    try:
        if spans is not None:
            window_span.__enter__()
            spans.active = True
        work.run(seconds)
    finally:
        if spans is not None:
            spans.uninstall()
            program.uninstall()
    window = Window(cell=cell, setup_s=setup_s, window_s=work.t_close - work.t_start,
                    loop=work, block_bytes=bb, stats0=stats0,
                    stats1=captured["stats1"], spans=captured.get("spans"),
                    program=captured.get("program"), peaks=peaks)
    if trace:
        window.trace = _read_trace(trace_dir, window.window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = _device_info(jax)
    compiles = captured["compiles"] - compiles0
    op = traffic["op"]
    log(f"[{cell.name}] set-up {setup_s:.3f} s, of which compile "
        f"{compile_s0:.3f} s over {compiles0} compiles; compiles in the "
        f"window: {compiles}")
    log(f"[{cell.name}] window {window.window_s:.3f} s: {len(work.samples)} "
        f"{op} issued, {len(work.in_window(op))} counted")

    failed = sum(1 for s in work.samples if not s.ok)
    result = {"correct": False, "attempted": len(work.samples),
              "failed": failed, "metrics": {}, "device": device}
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
    checks = {"failed_requests": (failed, 0, "<="),
              "compiles_in_window": (compiles, 0, "<=")}
    checks.update(runner.check(b, ref, work, cell, rngs["check"], stats_window))
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
