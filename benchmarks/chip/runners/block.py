"""A closed loop of block reads or block writes through the block service.

Traffic keys: ``op`` (``read`` or ``write``), ``address`` (a
``loadgen.addresses`` kind), ``request_blocks``, ``qd`` (requests
outstanding), ``check_mib``.  The client is ``loadgen.ClosedLoop`` in front
of a ``BlockDeviceService``; the window closes at the first completion past
the deadline, and its metrics count the requests completed by then.

Checks against the reference: every read the window answered; for writes,
at a seeded eighth of the acknowledgements, that the write is on the media
already, then after the drain a seeded ``check_mib`` of the acknowledged
extents read back healthy and again with as many drives failed as the
configuration survives, and the checksums stored beside the blocks.
"""
from __future__ import annotations

import numpy as np

import harness
from harness import MiB
from loadgen import AddressStream, ClosedLoop, PayloadSource
from reference import BlockReference

TENANT = "bench"
WARM_READS = 512
ACK_EVERY = 8


class AckCheck:
    """A write is acknowledged only once it has persisted: at every
    ``ACK_EVERY``-th acknowledgement, from a seeded phase, its blocks must
    be on the media already.  ``unpersisted`` counts those that are not."""

    def __init__(self, arr, loop: ClosedLoop, payloads: PayloadSource,
                 phase: int):
        self.arr = arr
        self.loop = loop
        self.payloads = payloads
        self.phase = phase
        self.unpersisted = 0

    def __call__(self, k: int) -> None:
        if k % ACK_EVERY == self.phase:
            self.unpersisted += self.unpersisted_blocks(k)

    def unpersisted_blocks(self, k: int) -> int:
        """Blocks of the ``k``-th write that are not on the media.  Each must
        read, where the L2P puts it, as its own payload or as a later payload
        to the same LBA (``writes[i]`` carries payload ``i``)."""
        writes = self.loop.samples
        n = writes[k].n_blocks
        lbas = writes[k].lba + np.arange(n)
        mapped, drives, zones, offs = harness.locate(self.arr, lbas)
        got, _ = harness.media(self.arr, drives, zones, offs, mapped)
        gi, gj = self.payloads.stamps(got)
        ok = mapped & (gi >= k) & (gi < len(writes)) & (gj >= 0) & (gj < n)
        idx = np.flatnonzero(ok)
        start = np.array([writes[g].lba for g in gi[idx]], np.int64)
        ok[idx] = (start + gj[idx] == lbas[idx]) & np.all(
            got[idx] == self.payloads.blocks(gi[idx], gj[idx], n), axis=1)
        return int((~ok).sum())


# the client's own work inside the window, kept out of the service's time
CLIENT = (
    ("client", PayloadSource, ("make",)),
    ("client", BlockReference, ("write",)),
    ("client", AckCheck, ("unpersisted_blocks",)),
)


def warm_up(b, traffic: dict, rng) -> None:
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


def prepare(b, ref: BlockReference, cell, rngs: dict) -> ClosedLoop:
    from repro.service import BlockDeviceService, QosClass

    traffic = cell.traffic
    warm_up(b, traffic, rngs["traffic"])
    svc = BlockDeviceService(b.pipe, max_inflight=traffic["qd"], policy="fifo")
    svc.register(TENANT, QosClass(TENANT, queue_cap=1 << 30))
    stream = AddressStream(traffic["address"], b.volume_blocks,
                           traffic["request_blocks"], rngs["traffic"])
    payloads = None
    if traffic["op"] == "write":
        payloads = PayloadSource(b.arr.zns_cfg.block_bytes, rngs["payload"])
    loop = ClosedLoop(svc, TENANT, traffic["op"], traffic["request_blocks"],
                      traffic["qd"], stream, ref, payloads)
    if payloads is not None:
        phase = int(rngs["check"].integers(ACK_EVERY))
        loop.on_ack = AckCheck(b.arr, loop, payloads, phase)
    return loop


def check(b, ref: BlockReference, loop: ClosedLoop, cell, rng,
          stats_window: dict) -> dict:
    """Every number compared, each with its limit and rule."""
    traffic, config = cell.traffic, cell.config
    checks = {}
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
    want = max(1, traffic["check_mib"] * MiB // (n * b.arr.zns_cfg.block_bytes))
    lbas = rng.choice(acked, size=min(want, acked.size), replace=False)
    checks["unpersisted_acked_blocks"] = (loop.on_ack.unpersisted, 0, "<=")
    checks["readback_mismatched_blocks"] = (
        harness.read_back(b, ref, lbas, n), 0, "<=")
    blocks = (lbas[:, None] + np.arange(n)).ravel()
    checks["crc_mismatched_blocks"] = (
        harness.crc_mismatches(b.arr, ref, blocks, rng, blocks.size), 0, "<=")
    losses = config["guarantee"]["drive_losses_survived"]
    failed_drives = sorted(rng.choice(b.arr.cfg.n_drives, losses, replace=False))
    d0 = b.arr.stats.degraded_reads
    for d in failed_drives:
        b.arr.fail_drive(int(d))
    checks["degraded_mismatched_blocks"] = (
        harness.read_back(b, ref, lbas, n), 0, "<=")
    checks["degraded_blocks_decoded"] = (b.arr.stats.degraded_reads - d0, 1, ">=")
    return checks
