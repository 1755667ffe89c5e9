"""Quickstart: a ZapRAID array in 40 lines.

Creates a (3+1)-RAID-5 array over four simulated ZNS drives with the
group-based Zone-Append layout, writes a few blocks, fails a drive, and
reads everything back through degraded decoding.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.array import ZapRaidConfig, ZapRAIDArray
from repro.core.zns import ZnsConfig

cfg = ZapRaidConfig(
    scheme="raid5", n_drives=4,
    group_size=16,        # G: stripes per Zone-Append group (paper 3.2)
    chunk_blocks=1, logical_blocks=512, gc_free_segments_low=1,
)
zns = ZnsConfig(n_zones=16, zone_cap_blocks=128, block_bytes=4096)
arr = ZapRAIDArray(cfg, zns)

rng = np.random.default_rng(0)
blocks = {lba: rng.integers(0, 256, (1, 4096), dtype=np.uint8) for lba in range(64)}
for lba, blk in blocks.items():
    arr.write(lba, blk)
arr.flush()
print(f"wrote 64 blocks; write amplification = {arr.stats.write_amp():.2f}")

seg = next(iter(arr.segments.values()))
print(f"CST for segment 0 (first group, per drive):\n{seg.cst.table[:, :8]}")

arr.fail_drive(2)
ok = all(np.array_equal(arr.read(l, 1)[0], b[0]) for l, b in blocks.items())
print(f"drive 2 failed -> all reads still correct: {ok} "
      f"(degraded reads: {arr.stats.degraded_reads}, "
      f"CST entries touched: {arr.stats.cst_entries_accessed})")

arr.rebuild_drive(2)
print("drive 2 rebuilt from survivors (full-drive recovery, paper 3.5)")
