"""Full-drive recovery: drives failed and rebuilt again and again, with no
foreground I/O.

Traffic keys: ``op`` (``rebuild``), ``rebuild_drives``, ``check_mib`` and
``check_blocks``.  A pass fails every drive of ``rebuild_drives``, then
rebuilds them one after another in that order, each through the timed
pipeline's rebuild actor (``HandlerPipeline.schedule_rebuild``, unpaced)
and the engine: the first decodes with as many erasures as there are
drives failed, the last with one.  Passes run back to back until the
wall-clock deadline; a pass that has started runs to its end, and the
window closes at the end of the last.  A pass's sample counts the blocks
that the replaced drives hold after it, the sum of their zones' write
pointers, and is ``ok`` when every drive is up again.  The warm-up is one
whole pass, which dispatches every decode shape that a pass does.

Checks, fed only from the seed:

* ``rebuilt_mismatched_blocks``: every block that the rebuilt drives held
  before the first failure (a copy of their written zones, taken after the
  prefill) against the drives after the window: its bytes bit-exact, its
  stored CRC32C equal to the reference's CRC32C of those bytes;
* ``rebuilt_readback_mismatched_blocks``: with as many of the other drives
  failed as the configuration survives, so that the blocks come from the
  rebuilt drives, a seeded ``check_mib`` of the volume in extents of
  ``check_blocks`` read through the array against the reference;
* ``rebuild_passes`` and ``rebuilt_blocks``: at least one of each.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np

import harness
from harness import MiB
from loadgen import Sample
from reference import BlockReference, crc32c_rows

CLIENT = ()
CRC_ROWS = 4096   # rows per reference CRC32C walk, a working set the cache holds


def rebuild_pass(pipe, drives) -> None:
    """Fail ``drives``, then rebuild each in turn, on the pipeline's engine."""
    eng = pipe.engine
    for d in drives:
        pipe.schedule_drive_failure(d, eng.now)
    for d in drives:
        pipe.schedule_rebuild(d, eng.now, interval_us=0.0)
    eng.run()


class RebuildPasses:
    """The window's work: whole rebuild passes until the deadline."""

    def __init__(self, b, drives: list, op: str):
        self.b = b
        self.drives = drives
        self.op = op
        self.samples: list[Sample] = []
        self.t_start = self.deadline = self.t_close = float("nan")
        self.on_close: Optional[Callable[[], None]] = None
        self.snapshot: dict = {}

    def take_snapshot(self) -> None:
        """Copy the written zones of the drives to be rebuilt."""
        for d in self.drives:
            drive = self.b.arr.drives[d]
            self.snapshot[d] = {int(z): drive.data[z, :drive.wp[z]].copy()
                                for z in np.flatnonzero(drive.wp)}

    def _pass(self) -> Sample:
        arr = self.b.arr
        s = Sample(self.op, -1, 0, time.perf_counter())
        rebuild_pass(self.b.pipe, self.drives)
        s.t_done = time.perf_counter()
        s.n_blocks = sum(int(arr.drives[d].wp.sum()) for d in self.drives)
        s.ok = not any(d.failed for d in arr.drives)
        self.samples.append(s)
        return s

    def run(self, seconds: float) -> None:
        self.t_start = time.perf_counter()
        self.deadline = self.t_start + seconds
        while True:
            s = self._pass()
            if s.t_done >= self.deadline:
                break
        self.t_close = s.t_done
        if self.on_close is not None:
            self.on_close()

    def in_window(self, op: str) -> list[Sample]:
        """Every pass that ended with the array whole: the window closes at
        the end of the last."""
        return [s for s in self.samples if s.op == op and s.ok]


def prepare(b, ref: BlockReference, cell, rngs: dict) -> RebuildPasses:
    work = RebuildPasses(b, [int(d) for d in cell.traffic["rebuild_drives"]],
                         cell.traffic["op"])
    work.take_snapshot()
    rebuild_pass(b.pipe, work.drives)   # the warm-up: one whole pass
    return work


def rebuilt_mismatches(arr, snapshot: dict) -> int:
    """Blocks of the snapshot that the drives no longer hold as they were,
    or whose stored CRC32C is not the reference's of those bytes."""
    bad = 0
    for d, zones in snapshot.items():
        drive = arr.drives[d]
        for z, want in zones.items():
            have = min(want.shape[0], int(drive.wp[z]))
            bad += want.shape[0] - have
            for i in range(0, have, CRC_ROWS):
                rows = want[i:i + CRC_ROWS]
                j = i + rows.shape[0]
                diff = np.any(drive.data[z, i:j] != rows, axis=1)
                diff |= drive.crc[z, i:j] != crc32c_rows(rows)
                bad += int(diff.sum())
    return bad


def check(b, ref: BlockReference, work: RebuildPasses, cell, rng,
          stats_window: dict) -> dict:
    """Every number compared, each with its limit and rule."""
    traffic, arr = cell.traffic, b.arr
    counted = work.in_window(traffic["op"])
    checks = {"rebuilt_mismatched_blocks": (
        rebuilt_mismatches(arr, work.snapshot), 0, "<=")}
    losses = cell.config["guarantee"]["drive_losses_survived"]
    others = [d for d in range(arr.cfg.n_drives) if d not in work.drives]
    for d in others[:losses]:
        arr.fail_drive(d)
    n = traffic["check_blocks"]
    extents = b.volume_blocks // n
    want = traffic["check_mib"] * MiB // (n * arr.zns_cfg.block_bytes)
    lbas = rng.choice(extents, size=min(max(1, want), extents), replace=False) * n
    checks["rebuilt_readback_mismatched_blocks"] = (
        harness.read_back(b, ref, lbas, n), 0, "<=")
    checks["rebuild_passes"] = (len(counted), 1, ">=")
    checks["rebuilt_blocks"] = (sum(s.n_blocks for s in counted), 1, ">=")
    return checks
