"""The checkpoint cell, ``ckpt.qwen2.5-3b.save``: its sizes at full width,
and whole runs at a small size on the CPU, sound and under every planted
fault."""
import math
import time

import pytest

from tinycell import TINY_CONFIG

import faults
import harness
import specs

CELL = "ckpt.qwen2.5-3b.save"
MiB = harness.MiB
# the state at smoke widths over two chips: 201 blocks a save, so a volume
# of 3 MiB (768 blocks) holds the manifest and a ring of three saves
TINY_TRAFFIC = {"smoke": True, "fsdp": 2, "volume_mib": 3, "check_blocks": 64}
# each fault and the number it fails on
FAULT_NUMBERS = {
    "parity_zero": "degraded_restored_mismatched_bytes",
    "half_batch": "degraded_restored_mismatched_bytes",
    "answer_flip": "degraded_restored_mismatched_bytes",
    "media_unchanged": "restored_mismatched_bytes",
    "crc_zero": "crc_mismatched_blocks",
    "ack_early": "unpersisted_at_manifest_blocks",
}


def tiny_cell(trace=False, **traffic):
    cell = specs.load_cell(CELL, trace=trace)
    cell.config.update(TINY_CONFIG)
    cell.traffic.update(TINY_TRAFFIC, **traffic)
    return cell


def run_tiny(*, trace=False, fault=None, seconds=0.5, seed=2**33 + 7, **traffic):
    with faults.planted(fault):
        return harness.run_cell(tiny_cell(trace, **traffic), seed, seconds, trace,
                                t_process=time.perf_counter(), require_tpu=False)


def test_tiny_cell_is_correct_and_reports_its_rate():
    r = run_tiny()
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"write_MiBps", "setup_s"}
    assert r["metrics"]["write_MiBps"]["value"] > 0
    checks = r["checks"]
    assert checks["saves"]["value"] == r["attempted"] >= 1 and r["failed"] == 0
    assert checks["saved_blocks"]["value"] == 201 * r["attempted"]
    assert checks["degraded_blocks_decoded"]["value"] >= 1


def test_tiny_cell_traced_reports_its_layer_metrics():
    r = run_tiny(trace=True)
    assert r["correct"], r["checks"]
    bench = specs.load_benchmark()
    new = {m["name"]: m for m in bench["per_layer"]
           if CELL in m.get("workloads", [])}
    assert len(new) == 8
    # the CPU reads no device metric: the idle share and the roofline need a
    # chip's trace
    spans = {n for n, m in new.items() if m["source"] == "program_span"}
    assert set(r["metrics"]) == spans
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_fails_its_own_number(fault):
    # the media left unchanged also fail the manifest and the persistence
    # check; each fault fails at least its own number
    r = run_tiny(fault=fault)
    assert not r["correct"]
    c = r["checks"][FAULT_NUMBERS[fault]]
    assert c["value"] > c["limit"], (fault, r["checks"])


def test_every_fault_has_its_number():
    assert set(FAULT_NUMBERS) == set(faults.FAULTS)


def test_a_volume_that_cannot_hold_the_ring_is_refused():
    with pytest.raises(harness.HarnessError, match="ring of 3 saves"):
        run_tiny(volume_mib=2)


def test_full_width_shard_is_647_MiB_in_57_leaves():
    import jax

    from repro.checkpoint.zapraid_ckpt import state_blocks

    cell = specs.load_cell(CELL, trace=False)
    t = cell.traffic
    shapes, global_shapes = cell.runner.shard_state(t["arch"], t["fsdp"], t["smoke"])
    leaves = jax.tree.leaves(shapes)
    nbytes = sum(math.prod(l.shape) * l.dtype.itemsize for l in leaves)
    assert len(leaves) == 57 and round(nbytes / MiB) == 647
    # the whole state: 14 bytes a parameter of Qwen2.5-3B's 3,085,938,688
    whole = sum(math.prod(l.shape) * l.dtype.itemsize
                for l in jax.tree.leaves(global_shapes))
    assert whole == 14 * 3_085_938_688 + 4
    # the largest extent, an f32 moment of w_gate or w_in: [36, 32, 11008]
    assert max(math.prod(l.shape) * l.dtype.itemsize for l in leaves) \
        == 36 * 32 * 11008 * 4 == 12384 * 4096
    # the volume is the smallest whole MiB that holds the manifest and a
    # ring of keep_last + 1 saves
    need = 64 + (t["keep_last"] + 1) * state_blocks(shapes, 4096)
    assert t["volume_mib"] == -(-need * 4096 // MiB)


def test_the_volume_gives_three_zones():
    cell = specs.load_cell(CELL, trace=False)
    cfg, zns = harness.array_configs(cell.config, cell.traffic)
    assert zns.n_zones == 3 and cfg.logical_blocks == 1942 * 256
    raid5 = specs.load_json("configs", "raid5-3p1")
    array_keys, zns_keys = specs.config_parts(cell.config)
    assert (array_keys, zns_keys) == specs.config_parts(raid5)
