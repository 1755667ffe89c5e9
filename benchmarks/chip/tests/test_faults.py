"""The comparison that decides ``correct`` fails the control and every
planted fault, and passes the whole path.

Each case drives a whole run of a cell at a small size on the CPU --
prefill, drive failures, warm-up, the window (the closed loop, or the
rebuild passes), the check -- with the timed path broken underneath
(``faults.py``), skipping only the harness's look for a chip.
``parity_zero`` is the control: acknowledged writes without their parity,
which breaks the configurations' guarantee that every acknowledged block
survives their drive losses.
"""
import pytest

from tinycell import run_tiny

import faults

CELLS = ("raid5.write.seq128k", "raid5.read.degraded4k",
         "raid6.write.seq128k", "raid6.read.degraded4k", "raid6.rebuild.2f")


@pytest.mark.parametrize("cell", CELLS)
def test_whole_path_is_correct(cell):
    r = run_tiny(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


# a read cell writes nothing in its window: no checksum to skip, no
# acknowledgement to give early
WRITE_ONLY = {"crc_zero", "ack_early"}
CASES = [(cell, fault) for fault in sorted(faults.FAULTS)
         for cell in ("raid5.write.seq128k", "raid6.write.seq128k",
                      "raid6.read.degraded4k")
         if fault not in WRITE_ONLY or ".write." in cell]
# the rebuild writes every block of the replaced drives, checksums
# included, but acknowledges nothing
CASES += [("raid6.rebuild.2f", fault) for fault in sorted(faults.FAULTS)
          if fault != "ack_early"]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(cell, fault):
    # long enough for the window to acknowledge writes it has only staged
    r = run_tiny(cell, fault=fault, seconds=2.0)
    assert not r["correct"], (fault, r["checks"])


@pytest.mark.parametrize("fault,number", [
    ("crc_zero", "crc_mismatched_blocks"),
    ("ack_early", "unpersisted_acked_blocks"),
])
def test_write_fault_fails_its_own_number(fault, number):
    r = run_tiny("raid5.write.seq128k", fault=fault, seconds=2.0)
    c = r["checks"][number]
    assert c["value"] > c["limit"], r["checks"]


def test_control_is_caught_on_every_cell_kind():
    # the control is what a later change would be tempted by: it fails the
    # degraded comparison of both a write and a read cell of RAID-5 too, and
    # both comparisons of the rebuild
    for cell in ("raid5.write.seq128k", "raid5.read.degraded4k",
                 "raid6.rebuild.2f"):
        r = run_tiny(cell, fault=faults.CONTROL)
        assert not r["correct"], r["checks"]
        bad = {k: v for k, v in r["checks"].items()
               if v["rule"] == "<=" and v["value"] > v["limit"]}
        assert bad and set(bad) <= {
            "degraded_mismatched_blocks", "read_mismatched_blocks",
            "rebuilt_mismatched_blocks", "rebuilt_readback_mismatched_blocks"}


def test_faults_are_removed_afterwards():
    from repro.core.zns import SimZnsDrive
    from repro.kernels import ops

    from repro.core import array, handlers

    def now():
        return (ops.xor_parity_batch_device, SimZnsDrive._commit_blocks,
                array.crc32c_many, handlers.HandlerPipeline._ev_write)

    before = now()
    for fault in faults.FAULTS:
        with faults.planted(fault):
            assert now() != before
    assert now() == before
