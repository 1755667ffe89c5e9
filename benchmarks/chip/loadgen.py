"""Traffic for the chip benchmark: address streams and a closed-loop client.

The address kinds are copied from ``repro.sim.workload._addresses`` and the
client follows ``repro.service.dispatcher.ClosedLoopClient``, so that a
change to the program cannot change the yardstick.  Unlike the program's
client, this one stamps every request with the host clock
(``time.perf_counter``) at submission and at its completion callback, and
stops issuing at a wall-clock deadline.  The virtual clock of the drive
model only orders events; nothing here reads it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np

from reference import BlockReference


def addresses(
    rng: np.random.Generator,
    kind: str,
    n_ops: int,
    logical_blocks: int,
    n_blocks: int,
    *,
    start: int = 0,
    hot_frac: float = 0.1,
    hot_prob: float = 0.8,
) -> np.ndarray:
    """Start LBAs in ``[0, logical_blocks - n_blocks]``; ``start`` is the
    first request index of the ``seq`` stream."""
    span = max(1, logical_blocks - n_blocks + 1)
    idx = np.arange(start, start + n_ops, dtype=np.int64)
    if kind == "seq":
        return (idx * n_blocks) % span
    if kind == "uniform":
        return rng.integers(0, span, n_ops)
    if kind == "hotspot":  # hot_prob of the ops on hot_frac of the space
        hot_span = max(1, int(span * hot_frac))
        hot = rng.random(n_ops) < hot_prob
        addr = rng.integers(0, span, n_ops)
        addr[hot] = rng.integers(0, hot_span, int(hot.sum()))
        return addr
    if kind == "zipf":  # heavy-tailed ranks scattered over the space
        ranks = rng.zipf(1.2, n_ops).astype(np.int64) % span
        return (ranks * np.int64(2654435761)) % span
    raise ValueError(f"unknown address kind: {kind}")


class AddressStream:
    """An endless seeded stream of start LBAs, drawn in batches."""

    BATCH = 4096

    def __init__(self, kind: str, volume_blocks: int, n_blocks: int,
                 rng: np.random.Generator):
        self.kind = kind
        self.volume_blocks = volume_blocks
        self.n_blocks = n_blocks
        self.rng = rng
        # a sequential stream starts at a seeded request index: each seed
        # does the same work, in another order
        per_pass = max(1, volume_blocks // n_blocks)
        self._start = int(rng.integers(0, per_pass)) if kind == "seq" else 0
        self._buf = np.zeros(0, np.int64)
        self._i = 0

    def next(self) -> int:
        if self._i == self._buf.size:
            self._buf = addresses(self.rng, self.kind, self.BATCH,
                                  self.volume_blocks, self.n_blocks,
                                  start=self._start)
            self._start += self.BATCH
            self._i = 0
        lba = int(self._buf[self._i])
        self._i += 1
        return lba


class PayloadSource:
    """Write payloads: blocks from a seeded pool, each stamped with a
    request counter and its block index so that no two writes carry the
    same bytes."""

    POOL_BLOCKS = 2048

    def __init__(self, block_bytes: int, rng: np.random.Generator):
        self.pool = np.frombuffer(
            bytearray(rng.bytes(self.POOL_BLOCKS * block_bytes)), np.uint8
        ).reshape(self.POOL_BLOCKS, block_bytes)
        self.stamp0 = int(rng.integers(1 << 62))
        self.count = 0

    def make(self, n_blocks: int) -> np.ndarray:
        i = self.count
        self.count += 1
        return self.blocks(np.full(n_blocks, i), np.arange(n_blocks), n_blocks)

    def blocks(self, i: np.ndarray, j: np.ndarray, n_blocks: int) -> np.ndarray:
        """Block ``j`` of the ``i``-th payload of ``n_blocks`` blocks, for
        each pair ``(i, j)``."""
        i = np.asarray(i, np.int64)
        j = np.asarray(j, np.int64)
        out = self.pool[(i * n_blocks + j) % self.POOL_BLOCKS]  # a fresh array
        stamp = out[:, :16].view(np.uint64)
        stamp[:, 0] = (np.uint64(self.stamp0) + i.astype(np.uint64))
        stamp[:, 1] = j.astype(np.uint64)
        return out

    def stamps(self, blocks: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """``(i, j)`` read back from the stamps of blocks this source made."""
        stamp = np.ascontiguousarray(blocks[:, :16]).view(np.uint64)
        i = (stamp[:, 0] - np.uint64(self.stamp0)).astype(np.int64)
        return i, stamp[:, 1].astype(np.int64)


@dataclasses.dataclass
class Sample:
    op: str
    lba: int
    n_blocks: int
    t_submit: float
    t_done: float = float("nan")
    ok: bool = False
    result: Optional[np.ndarray] = None


class ClosedLoop:
    """``qd`` requests outstanding; each completion issues the next until
    the wall-clock deadline, after which the loop drains what is in flight.

    Writes update the reference at submission: the array lets the later of
    two writes to one LBA win, in issue order."""

    REAP_EVERY = 256

    def __init__(self, svc, tenant: str, op: str, n_blocks: int, qd: int,
                 stream: AddressStream, ref: BlockReference,
                 payloads: Optional[PayloadSource] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.svc = svc
        self.tenant = tenant
        self.op = op
        self.n_blocks = n_blocks
        self.qd = qd
        self.stream = stream
        self.ref = ref
        self.payloads = payloads
        self.clock = clock
        self.samples: list[Sample] = []
        self.t_start = self.deadline = self.t_close = float("nan")
        self.closed = False
        self.on_close: Optional[Callable[[], None]] = None
        # called with a write's index as it is acknowledged
        self.on_ack: Optional[Callable[[int], None]] = None
        self._done = 0

    def _issue(self) -> None:
        lba = self.stream.next()
        if self.op == "write":
            data = self.payloads.make(self.n_blocks)
            self.ref.write(lba, data)
            s = Sample("write", lba, self.n_blocks, self.clock())
            k = len(self.samples)
            self.samples.append(s)
            self.svc.submit_write(self.tenant, lba, data,
                                  cb=lambda req, s=s, k=k: self._on_done(s, req, k))
        else:
            s = Sample("read", lba, self.n_blocks, self.clock())
            self.samples.append(s)
            self.svc.submit_read(self.tenant, lba, self.n_blocks,
                                 cb=lambda req, s=s: self._on_done(s, req))

    def _close(self, now: float) -> None:
        self.closed = True
        self.t_close = now
        if self.on_close is not None:
            self.on_close()

    def _on_done(self, s: Sample, req, k: int = -1) -> None:
        now = self.clock()
        s.t_done = now
        s.ok = req.ok()
        if s.op == "read":
            s.result = req.result
        elif s.ok and self.on_ack is not None:
            self.on_ack(k)
        self._done += 1
        if self._done % self.REAP_EVERY == 0:
            self.svc.cq.drain()  # reap completions, as a client of the CQ does
        if not self.closed and now >= self.deadline:
            self._close(now)
        if not self.closed:
            self._issue()

    def run(self, seconds: float) -> None:
        """Measure for ``seconds`` of wall time, then drain."""
        self.t_start = self.clock()
        self.deadline = self.t_start + seconds
        for _ in range(self.qd):
            self._issue()
        self.svc.engine.run()
        if not self.closed:  # every request finished before the deadline
            self._close(self.clock())
        self.svc.drain()
        self.svc.cq.drain()

    # -- what the window measured -------------------------------------------

    def in_window(self, op: str) -> list[Sample]:
        """Requests of ``op`` completed, successfully, by the deadline."""
        return [s for s in self.samples
                if s.op == op and s.ok and s.t_done <= self.deadline]
