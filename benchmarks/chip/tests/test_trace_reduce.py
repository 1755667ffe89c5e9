"""The reduction from a trace to device busy time, idle gaps and program
time, the byte counts of the kernels at the cells' shapes, and the peaks
table, on hand-built inputs."""
import pytest

from tinycell import CHIP  # noqa: F401  (puts the benchmark on the path)

import harness
import specs
import tracereduce as tr

OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE


def test_busy_is_the_union_of_overlapping_ops():
    ev = [(OPS, "a", 0, 10), (OPS, "b", 5, 10), (OPS, "c", 30, 5),
          (MODS, "jit_f", 0, 40)]   # modules do not count where ops exist
    assert tr.busy_ns(ev, 0, 100) == 20
    assert tr.busy_ns(ev, 8, 32) == (15 - 8) + (32 - 30)


def test_busy_falls_back_to_modules_without_an_op_line():
    ev = [(MODS, "jit_f", 10, 20), (MODS, "jit_g", 25, 10)]
    assert tr.busy_ns(ev, 0, 100) == 25


def test_idle_gaps_cover_the_rest_of_the_window():
    ev = [(OPS, "a", 10, 10), (OPS, "b", 15, 10), (OPS, "c", 50, 10)]
    gaps = tr.idle_gaps(ev, 0, 100)
    assert gaps == [(0, 10), (25, 50), (60, 100)]
    assert sum(e - s for s, e in gaps) + tr.busy_ns(ev, 0, 100) == 100
    assert tr.idle_gaps([], 0, 5) == [(0, 5)]


def test_module_time_matches_jit_names_with_or_without_an_id():
    ev = [(MODS, "jit_xor_parity(12)", 0, 7), (MODS, "jit_xor_parity", 20, 3),
          (MODS, "jit_xor_parity_batch_device(3)", 40, 100),
          (OPS, "jit_xor_parity", 0, 1000)]
    assert tr.module_ns(ev, ("xor_parity",)) == 10
    assert tr.module_ns(ev, ("xor_parity_batch_device",)) == 100
    assert tr.module_ns(ev, ("rs_matmul",)) == 0


def test_top_ops_sums_by_program_and_op_in_seconds():
    ev = [(MODS, "jit_f(1)", 0, 3e9), (MODS, "jit_g(2)", 4e9, 4e9),
          (OPS, "%x = s32[8] copy(%a)", 0, 2e9), (OPS, "%y = s32[8] add(%a)", 2e9, 1e9),
          (OPS, "%x = s32[8] copy(%b)", 5e9, 2e9), (OPS, "%z", 9e9, 1e9)]
    assert tr.top_ops(ev) == [["jit_f/x", 2.0], ["jit_g/x", 2.0],
                              ["jit_f/y", 1.0], ["z", 1.0]]
    assert tr.top_ops(ev, 1) == [["jit_f/x", 2.0]]


def test_gaps_go_to_the_innermost_host_span_over_each_stretch():
    host = [("array:write", 0, 100), ("codec:materialize", 40, 20),
            ("client:make", 200, 10)]
    # the second gap spans array:write, then codec:materialize, then
    # array:write again; the third lies partly outside every span
    gaps = [(10, 20), (30, 70), (150, 170), (195, 205)]
    got = dict(tr.attribute_gaps(gaps, host))
    assert got == pytest.approx({"array:write": 30e-9,
                                 "codec:materialize": 20e-9,
                                 "service": 25e-9, "client:make": 5e-9})
    total = sum(e - s for s, e in gaps) * 1e-9
    assert sum(got.values()) == pytest.approx(total)


def test_bytes_at_the_cells_shapes():
    k = specs.load_kernels()
    xor, rs = k["xor"].BYTES, k["rs"].BYTES
    # RAID-5 group commit: 256 stripes x 3 chunks of 4 KiB in, parity out
    assert xor["xor_parity_batch_device"](((256, 3, 1024),)) == 4 * (256 * 1024 * 4)
    # its metadata: 16 bytes a block
    assert xor["xor_parity_batch_device"](((256, 3, 4),)) == 4 * 256 * 4 * 4
    # RAID-5 degraded 4 KiB read: three survivors in, the lost chunk out
    assert xor["xor_parity"](((3, 1024),)) == 4 * 4096
    # RAID-6 group commit: 2 data in, 2 parity out, plus the 2x2 matrix
    assert rs["rs_matmul_batch_device"](((2, 2), (256, 2, 1024))) \
        == 4 * (4 + 2 * 256 * 2 * 1024)
    # RAID-6 degraded read: 2 survivors in, 2 data chunks out
    assert rs["rs_matmul"](((2, 2), (2, 1024))) == 4 * (4 + 2 * 2048)


def test_peaks_are_looked_up_by_device_kind():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
