"""Bring-up check: the ZapRAID main path on one TPU chip, at full size.

Run from the repository root, on a machine with a TPU:

    python3 chip_smoke.py

One process, no children.  Phases, each through the entry points a user
calls, each checked against a plain reference and failing hard:

1. block service, RAID-5 (3+1), the paper's default: 4 KiB blocks, G=256,
   4 KiB chunks, 1 GiB written sequentially in 128 KiB requests, then
   16,384 random 4 KiB overwrites; everything read back healthy, with one
   drive failed (every read of it goes through the XOR decode kernel), and
   after the drive is rebuilt -- each pass compared with a dict-of-blocks
   reference fed from the same seed;
2. block service, RAID-6 (2+2): the same steps over 256 MiB with two drives
   failed (RS encode and RS decode);
3. trainer: ``repro.launch.train`` at the published widths of smollm-135m,
   6 steps with a ZapRAID RAID-5 checkpoint every 3, one lane failed, the
   step-6 checkpoint restored through the degraded path and compared bit
   for bit with the live state, then 2 more steps from each state, whose
   losses must be identical;
4. proof that the codec ran as compiled Pallas kernels: every codec call of
   the run was resolved to ``use_pallas=True, interpret=False`` and its
   lowering holds a ``tpu_custom_call``.

Every time printed is host wall time.  The last line of standard output is
one JSON object naming the device; nothing follows it.  Without a TPU the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

KiB = 1 << 10
MiB = 1 << 20
BLOCK = 4 * KiB
ZONE_BLOCKS = 16384          # 64 MiB zones (see _zone_cut)
ZN540_ZONE_MIB = 1077        # the paper's drive: 1,077 MiB zone capacity
OVERWRITES = 16384           # random 4 KiB overwrites per block-service phase
WRITE_BLOCKS = 32            # 128 KiB write requests
READ_BLOCKS = 256            # 1 MiB read requests
TRAIN_ARGV = [
    "--arch", "smollm-135m", "--no-smoke", "--steps", "6", "--ckpt-every", "3",
    "--global-batch", "8", "--seq-len", "512", "--fail-lane", "1", "--fail-at", "6",
]
CODEC_OPS = ("xor_parity", "rs_matmul", "xor_parity_batch", "rs_matmul_batch",
             "xor_parity_batch_device", "rs_matmul_batch_device")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit records
    its retrieval time instead, so a warm cache shows as fewer seconds)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


class CodecCalls:
    """Records every codec dispatch of the run, keyed by op and shapes.

    Wraps the jitted entry points of ``repro.kernels.ops`` (the codec looks
    them up on the module at call time) and restores them on exit."""

    def __init__(self):
        from repro.kernels import ops

        self.ops = ops
        self.originals = {name: getattr(ops, name) for name in CODEC_OPS}
        self.calls: dict[tuple, int] = {}

    def _wrap(self, name: str):
        fn = self.originals[name]

        def recorded(*args, **kw):
            sig = tuple((tuple(a.shape), str(a.dtype)) for a in args)
            key = (name, sig, tuple(sorted(kw.items())))
            self.calls[key] = self.calls.get(key, 0) + 1
            return fn(*args, **kw)

        return recorded

    def __enter__(self) -> "CodecCalls":
        for name in CODEC_OPS:
            setattr(self.ops, name, self._wrap(name))
        return self

    def __exit__(self, *exc) -> None:
        for name, fn in self.originals.items():
            setattr(self.ops, name, fn)


def timed(clock: CompileClock, label: str, fn, *args):
    """Run one phase; print its host wall and compile seconds."""
    c0, n0, t0 = clock.seconds, clock.count, time.perf_counter()
    out = fn(*args)
    print(f"[{label}] host wall {time.perf_counter() - t0:.2f} s, of which "
          f"compile {clock.seconds - c0:.2f} s over {clock.count - n0} "
          f"compiles", flush=True)
    return out


def _zone_cut(zone_blocks: int) -> str:
    return (f"zones cut to {zone_blocks} blocks ({zone_blocks * BLOCK // MiB} "
            f"MiB) from the ZN540's {ZN540_ZONE_MIB} MiB: the simulated drives "
            f"hold their zones as dense host arrays")


# ------------------------------------------------------------ block service

def _read_pass(svc, ref: dict, n_blocks: int, label: str) -> None:
    """Read every LBA through the service; compare with the reference."""
    for lba in range(0, n_blocks, READ_BLOCKS):
        svc.submit_read("smoke", lba, min(READ_BLOCKS, n_blocks - lba))
    svc.drain()
    bad = blocks = 0
    for req in svc.cq.drain():
        require(req.ok(), f"{label}: read at lba {req.lba} ended {req.status}")
        for i, row in enumerate(req.result):
            bad += row.tobytes() != ref[req.lba + i]
            blocks += 1
    print(f"  {label} read: {bad} mismatches in {blocks} blocks", flush=True)
    require(blocks == n_blocks, f"{label}: read {blocks} of {n_blocks} blocks")
    require(bad == 0, f"{label}: {bad} blocks read back wrong")


def block_service(scheme: str, data_bytes: int, failed: tuple[int, ...],
                  *, zone_blocks: int = ZONE_BLOCKS,
                  overwrites: int = OVERWRITES, seed: int = 0) -> dict:
    """Write, overwrite and read back a volume healthy, degraded and rebuilt
    through ``BlockDeviceService``; returns the array's ``Stats``."""
    from repro.core.array import ZapRaidConfig
    from repro.core.handlers import HandlerPipeline
    from repro.core.raid import make_scheme
    from repro.core.segment import solve_stripes_per_segment
    from repro.core.zns import ZnsConfig
    from repro.service import BlockDeviceService, QosClass

    n_drives = 4
    n_blocks = data_bytes // BLOCK
    k = make_scheme(scheme, n_drives).k
    stripes, _ = solve_stripes_per_segment(zone_blocks, 1, BLOCK)
    # a drive's share of the data and the overwrites, half again for GC
    # headroom, plus the open segment and the GC watermark
    n_zones = math.ceil(1.5 * (n_blocks + overwrites) / (k * stripes)) + 2
    cfg = ZapRaidConfig(scheme=scheme, n_drives=n_drives, group_size=256,
                        chunk_blocks=1, logical_blocks=n_blocks)
    zns = ZnsConfig(n_zones=n_zones, zone_cap_blocks=zone_blocks, block_bytes=BLOCK)
    pipe = HandlerPipeline.build_timed(cfg, zns, seed=seed)
    arr = pipe.array
    svc = BlockDeviceService(pipe, max_inflight=64, policy="fifo")
    svc.register("smoke", QosClass("smoke", queue_cap=n_blocks + overwrites))
    print(f"  {scheme} ({k}+{n_drives - k}): {n_drives} drives x {n_zones} "
          f"zones, G=256, 4 KiB chunks; codec use_pallas={arr.codec.use_pallas} "
          f"interpret={arr.codec.interpret}; {_zone_cut(zone_blocks)}", flush=True)

    rng = np.random.default_rng(seed)
    data = np.frombuffer(bytearray(rng.bytes(n_blocks * BLOCK)), np.uint8)
    data = data.reshape(n_blocks, BLOCK)
    ref = {lba: data[lba].tobytes() for lba in range(n_blocks)}

    t0 = time.perf_counter()
    for lba in range(0, n_blocks, WRITE_BLOCKS):
        svc.submit_write("smoke", lba, data[lba:lba + WRITE_BLOCKS])
    lbas = rng.integers(0, n_blocks, overwrites)
    fresh = np.frombuffer(bytearray(rng.bytes(overwrites * BLOCK)), np.uint8)
    fresh = fresh.reshape(overwrites, BLOCK)
    for i, lba in enumerate(lbas):
        svc.submit_write("smoke", int(lba), fresh[i:i + 1])
        ref[int(lba)] = fresh[i].tobytes()
    svc.drain()
    acks = svc.cq.drain()
    require(len(acks) == n_blocks // WRITE_BLOCKS + overwrites
            and all(r.ok() for r in acks), f"{scheme}: a write was not acked")
    print(f"  {scheme} wrote {data_bytes // MiB} MiB sequentially in 128 KiB "
          f"requests + {overwrites} random 4 KiB overwrites: host wall "
          f"{time.perf_counter() - t0:.2f} s", flush=True)

    _read_pass(svc, ref, n_blocks, f"{scheme} healthy")
    for d in failed:
        arr.fail_drive(d)
    before = arr.stats.degraded_reads
    _read_pass(svc, ref, n_blocks, f"{scheme} degraded (drives {failed} failed)")
    require(arr.stats.degraded_reads > before,
            f"{scheme}: no read went through reconstruction")
    t0 = time.perf_counter()
    for d in failed:
        arr.rebuild_drive(d)
    print(f"  {scheme} rebuilt drives {failed}: host wall "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    _read_pass(svc, ref, n_blocks, f"{scheme} rebuilt")
    s = arr.stats
    print(f"  {scheme} bytes: host written "
          f"{(n_blocks + overwrites) * BLOCK}, device written "
          f"{s.device_blocks_written * BLOCK}, host read {3 * n_blocks * BLOCK}; "
          f"degraded blocks {s.degraded_reads}; h2d {s.h2d_copies} copies "
          f"{s.h2d_bytes} B, d2h {s.d2h_copies} copies {s.d2h_bytes} B",
          flush=True)
    return s


# ------------------------------------------------------------------ trainer

def trainer(argv: list[str]) -> None:
    """Train, checkpoint, fail a lane, restore degraded, continue both."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch import train

    tr = train.run(argv)
    args = train.parse_args(argv)
    if not args.smoke:
        require(tr.cfg == get_config(args.arch), "trainer is not at full width")
    n_params = sum(x.size for x in jax.tree.leaves(tr.params))
    print(f"  {args.arch}: {tr.cfg.n_layers} layers, d_model {tr.cfg.d_model}, "
          f"vocab {tr.cfg.vocab}, {tr.cfg.dtype}; {n_params} parameters",
          flush=True)
    eng = tr.engine
    require(any(d.failed for d in eng.array.drives), "no checkpoint lane failed")
    live = tr.state()
    step = max(eng.catalog)
    before = eng.array.stats.degraded_reads
    t0 = time.perf_counter()
    restored = eng.restore(step, live)
    print(f"  restored step {step} degraded: host wall "
          f"{time.perf_counter() - t0:.2f} s, "
          f"{eng.array.stats.degraded_reads - before} blocks reconstructed",
          flush=True)
    require(eng.array.stats.degraded_reads > before,
            "restore did not take the degraded path")
    leaves = list(zip(jax.tree.leaves(live), jax.tree.leaves(restored)))
    bad = sum(
        np.asarray(a).dtype != b.dtype or np.asarray(a).tobytes() != b.tobytes()
        for a, b in leaves
    )
    nbytes = sum(b.nbytes for _, b in leaves)
    print(f"  restored state vs live: {bad} mismatching leaves of {len(leaves)} "
          f"({nbytes} bytes)", flush=True)
    require(bad == 0, f"{bad} restored leaves differ from the live state")

    a, b = live, jax.tree.map(jnp.asarray, restored)
    for s in range(step, step + 2):
        a, loss_a = tr.step(a, s)
        b, loss_b = tr.step(b, s)
        print(f"  step {s + 1}: loss from live {loss_a!r}, from restored "
              f"{loss_b!r}", flush=True)
        require(loss_a == loss_b, f"step {s + 1}: losses differ after restore")
    s = eng.array.stats
    print(f"  checkpoint bytes: device written {s.device_blocks_written * BLOCK}; "
          f"h2d {s.h2d_copies} copies {s.h2d_bytes} B, d2h {s.d2h_copies} "
          f"copies {s.d2h_bytes} B", flush=True)


# ---------------------------------------------------------- compile proof

def check_compiled(calls: CodecCalls) -> None:
    """Every recorded codec call ran the compiled kernel: mode resolved to
    Pallas without interpret, and its lowering holds ``tpu_custom_call``."""
    import jax

    require(calls.calls, "no codec call was recorded")
    for (name, sig, kw), n in sorted(calls.calls.items()):
        mode = dict(kw)
        shapes = ", ".join(f"{d}{list(s)}" for s, d in sig)
        require(mode.get("use_pallas") is True and mode.get("interpret") is False,
                f"{name}({shapes}) ran with {mode}")
        args = [jax.ShapeDtypeStruct(s, d) for s, d in sig]
        text = calls.originals[name].lower(*args, **mode).as_text()
        found = "tpu_custom_call" in text
        print(f"  {name}({shapes}) x{n}: tpu_custom_call "
              f"{'present' if found else 'MISSING'}", flush=True)
        require(found, f"{name}({shapes}) lowered without a Pallas TPU kernel")


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
                 f"({dev.device_kind})")
    from repro.launch.compile_cache import use_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; compile cache {use_compile_cache()}",
          flush=True)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    t0 = time.perf_counter()
    with CodecCalls() as calls:
        timed(clock, "block service raid5 1 GiB", block_service,
              "raid5", 1024 * MiB, (1,))
        timed(clock, "block service raid6 256 MiB", block_service,
              "raid6", 256 * MiB, (0, 2))
        timed(clock, "trainer smollm-135m", trainer, TRAIN_ARGV)
    timed(clock, "compiled-kernel check", check_compiled, calls)
    print(f"total: host wall {time.perf_counter() - t0:.2f} s, compile "
          f"{clock.seconds:.2f} s over {clock.count} compiles", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
