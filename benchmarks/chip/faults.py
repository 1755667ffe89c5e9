"""The control and the planted faults that the comparison has to catch.

Each entry breaks the timed path underneath a run, from outside the
program (class methods and module attributes replaced for the run, put
back afterwards):

* ``parity_zero`` -- the control: the configuration's guarantee broken.
  Every stripe group's encode returns zeros, so a write is acknowledged
  without the parity that lets it survive a drive loss;
* ``media_unchanged`` -- a step that returns its state unchanged: the
  drives take every append and advance their write pointers, but the
  media keep what they held before;
* ``half_batch`` -- half of the batch left out: each stripe group's encode
  covers only its first half of the stripes, the rest get zero parity;
* ``answer_flip`` -- an answer altered where it is produced: every codec
  program's result has its first lane flipped;
* ``crc_zero`` -- the checksum layer skipped: every block is stored with a
  zero CRC32C in place of its own;
* ``ack_early`` -- a write acknowledged when it is staged, before its
  stripe and parity have persisted.

The exchange between chips does not exist in a one-chip cell.

In the rebuild cell (``runners/rebuild.py``) the fault stands from the
prefill on: ``parity_zero``, ``half_batch`` and ``answer_flip`` leave
survivors from which the replaced drives' blocks do not decode, and a
flipped decode writes a wrong block; ``media_unchanged`` and ``crc_zero``
leave the replaced drives without the blocks or their checksums.  It
acknowledges no write, so ``ack_early`` has nothing to break there.

Run the control at a cell's own size on the chip with

    python3 benchmarks/chip/faults.py --workload <cell> --seed <n> \
        --seconds <s> --fault parity_zero

which prints the same result line as ``run.py``; ``correct`` must read
false.  ``tests/test_faults.py`` plants every fault at a small size on
the CPU.
"""
from __future__ import annotations

import contextlib
import importlib

import numpy as np

ENCODE_OPS = ("xor_parity_batch_device", "rs_matmul_batch_device")
ALL_OPS = ("xor_parity", "rs_matmul", "xor_parity_batch", "rs_matmul_batch",
           "xor_parity_batch_device", "rs_matmul_batch_device")


def _zero(fn):
    def broken(*args, **kw):
        out = fn(*args, **kw)
        return out * 0
    return broken


def _half(fn):
    def broken(*args, **kw):
        out = fn(*args, **kw)
        keep = (out.shape[0] + 1) // 2
        return out.at[keep:].set(0)
    return broken


def _flip(fn):
    def broken(*args, **kw):
        out = fn(*args, **kw)
        first = (0,) * out.ndim
        return out.at[first].set(out[first] ^ 1)
    return broken


def _media_unchanged(orig):
    def broken(self, zone, blocks, oobs, crcs=None):
        off = int(self.wp[zone])
        before = self.data[zone, off:off + blocks.shape[0]].copy()
        orig(self, zone, blocks, oobs, crcs)
        self.data[zone, off:off + blocks.shape[0]] = before
    return broken


def _crc_zero(fn):
    def broken(blocks, *args, **kw):
        return np.zeros_like(fn(blocks, *args, **kw))
    return broken


def _ack_early(orig):
    def broken(self, lba, data, cb, tenant, t_submit):
        orig(self, lba, data, None, tenant, t_submit)
        if cb is not None:
            self.engine.at(self.engine.now, cb, self.engine.now)
    return broken


# fault -> [(module, class or None, attribute, wrapper)]
FAULTS = {
    "parity_zero": [("repro.kernels.ops", None, op, _zero) for op in ENCODE_OPS],
    "half_batch": [("repro.kernels.ops", None, op, _half) for op in ENCODE_OPS],
    "answer_flip": [("repro.kernels.ops", None, op, _flip) for op in ALL_OPS],
    "media_unchanged": [("repro.core.zns", "SimZnsDrive", "_commit_blocks",
                         _media_unchanged)],
    "crc_zero": [(mod, None, "crc32c_many", _crc_zero)
                 for mod in ("repro.core.array", "repro.core.zns")],
    "ack_early": [("repro.core.handlers", "HandlerPipeline", "_ev_write",
                   _ack_early)],
}
CONTROL = "parity_zero"


@contextlib.contextmanager
def planted(fault: str | None):
    """Break the timed path with ``fault`` (``None``: leave it whole)."""
    if fault is None:
        yield
        return
    undo = []
    try:
        for mod_name, cls_name, attr, wrapper in FAULTS[fault]:
            mod = importlib.import_module(mod_name)
            owner = getattr(mod, cls_name) if cls_name else mod
            original = owner.__dict__[attr] if cls_name else getattr(owner, attr)
            undo.append((owner, attr, original))
            setattr(owner, attr, wrapper(original))
        yield
    finally:
        for owner, attr, original in reversed(undo):
            setattr(owner, attr, original)


if __name__ == "__main__":
    import run
    run.main(faults=tuple(FAULTS))
