"""Shared by the benchmark's tests: paths, and a cell cut to a size that
the CPU runs in a second (the codec on its jnp reference)."""
import pathlib
import sys
import time

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import specs  # noqa: E402

# small zones and groups, a 2 MiB volume: every layer runs, GC included
TINY_CONFIG = {"zone_cap_blocks": 512, "group_size": 16}
TINY_TRAFFIC = {"volume_mib": 2, "prefill_mib": 2, "check_mib": 1}
# the most a runner's own traffic key may be at the tiny size, for the
# runners whose traffic has it: as at full size, far fewer blocks in flight
# than the volume, and a request a fraction of a stripe group
TINY_MOST = {"qd": 4, "request_blocks": 8}


def tiny_cell(name: str, trace: bool = False):
    cell = specs.load_cell(name, trace=trace)
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC)
    for key, most in TINY_MOST.items():
        if key in cell.traffic:
            cell.traffic[key] = min(cell.traffic[key], most)
    return cell


def run_tiny(name: str, *, seed: int = 2**33 + 5, seconds: float = 0.5,
             trace: bool = False, fault=None) -> dict:
    import faults
    import harness

    cell = tiny_cell(name, trace)
    with faults.planted(fault):
        return harness.run_cell(cell, seed, seconds, trace,
                                t_process=time.perf_counter(),
                                require_tpu=False)
