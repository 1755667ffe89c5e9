"""The plain reference of the checkpoint cell: every save as it was handed
over, by step and leaf, and the blocks it lays down in the volume.

It imports nothing of the program.  It is fed from host copies of each
save's state (``jax.device_get`` of the arrays the save is given), taken as
the save is submitted, and places each save by the volume's documented
layout on its own:

* the volume's first ``MANIFEST_BLOCKS`` blocks hold the manifest;
* a save lays its leaves in order, each in whole blocks (at least one),
  its bytes first and zeros after, right after the leaf before it;
* a leaf that would run past the end of the volume starts again right
  after the manifest: the volume is a ring, and a save overwrites the
  oldest save's blocks.

The bytes live in a :class:`reference.BlockReference` at those blocks; a
leaf's bytes are read back from there.  The ring holds ``keep_last + 1``
saves, so no save the reference keeps is overwritten while it is kept.
Bytes are compared as bytes (``uint8`` views), never as floating-point
values: drawn bits include NaN patterns, which compare unequal to
themselves.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

from reference import BlockReference

MANIFEST_BLOCKS = 64
# the manifest's fields that the reference also knows, per leaf
MANIFEST_FIELDS = ("lba", "n_blocks", "nbytes", "dtype", "shape",
                   "global_shape", "start")


@dataclasses.dataclass
class Leaf:
    lba: int
    n_blocks: int
    nbytes: int
    dtype: str
    shape: list
    global_shape: list
    start: list

    def entry(self) -> dict:
        return {k: getattr(self, k) for k in MANIFEST_FIELDS}


class CkptReference:
    """Step -> leaf path -> :class:`Leaf`, for the last ``keep_last``
    saves, over the volume's blocks."""

    def __init__(self, blocks: BlockReference, keep_last: int):
        self.blocks = blocks
        self.keep_last = keep_last
        self.block_bytes = blocks.blocks.shape[1]
        self.steps: dict[int, dict[str, Leaf]] = {}
        self._next = MANIFEST_BLOCKS

    def _place(self, n_blocks: int) -> int:
        if self._next + n_blocks > self.blocks.blocks.shape[0]:
            self._next = MANIFEST_BLOCKS
        lba = self._next
        self._next += n_blocks
        return lba

    def record(self, step: int, leaves) -> None:
        """Take one save: ``leaves`` is ``[(path, host array, global
        shape, start)]`` in the order the save lays them down."""
        bb = self.block_bytes
        saved = {}
        for path, arr, global_shape, start in leaves:
            raw = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
            n_blocks = max(1, -(-raw.size // bb))
            lba = self._place(n_blocks)
            flat = self.blocks.blocks[lba:lba + n_blocks].reshape(-1)
            flat[:raw.size] = raw
            flat[raw.size:] = 0
            saved[path] = Leaf(lba, n_blocks, int(raw.size), str(arr.dtype),
                               list(arr.shape), list(global_shape), list(start))
        self.steps[step] = saved
        for old in sorted(self.steps)[:-self.keep_last]:
            del self.steps[old]

    def last_step(self) -> int:
        return max(self.steps)

    def leaf_bytes(self, step: int, path: str) -> np.ndarray:
        leaf = self.steps[step][path]
        flat = self.blocks.blocks[leaf.lba:leaf.lba + leaf.n_blocks].reshape(-1)
        return flat[:leaf.nbytes]

    def lbas(self, step: int) -> np.ndarray:
        """Every block the save of ``step`` lays down."""
        return np.concatenate([np.arange(l.lba, l.lba + l.n_blocks)
                               for l in self.steps[step].values()])

    def sample_lbas(self, step: int, rng, n: int) -> np.ndarray:
        """The last block of every leaf, then seeded others of the save,
        ``n`` in all (every block where the save has no more)."""
        every = self.lbas(step)
        last = np.array([l.lba + l.n_blocks - 1
                         for l in self.steps[step].values()], np.int64)
        rest = np.setdiff1d(every, last)
        pick = rng.choice(rest, size=min(max(0, n - last.size), rest.size),
                          replace=False)
        return np.sort(np.concatenate([last, pick]))

    def mismatched_bytes(self, step: int, restored: dict) -> int:
        """Bytes of the save of ``step`` that ``restored`` (leaf path ->
        array) does not hold as saved: a leaf missing, or of another dtype
        or shape, counts all of its bytes."""
        bad = 0
        for path, leaf in self.steps[step].items():
            got = restored.get(path)
            if got is None or str(got.dtype) != leaf.dtype \
                    or list(got.shape) != leaf.shape:
                bad += leaf.nbytes
                continue
            got = np.ascontiguousarray(got).reshape(-1).view(np.uint8)
            bad += int(np.count_nonzero(got != self.leaf_bytes(step, path)))
        return bad

    def manifest_mismatches(self, manifest: dict) -> int:
        """Entries of ``manifest`` (step -> {"leaves": {path: entry}}, as
        read back from the volume) that disagree with the reference: each
        step kept on one side only, and each leaf of the last step missing
        on one side or with a field of :data:`MANIFEST_FIELDS` that
        differs."""
        have = {int(s): m for s, m in manifest.items()}
        bad = len(set(have) ^ set(self.steps))
        last = self.last_step()
        got = have.get(last, {}).get("leaves", {})
        want = self.steps[last]
        for path in set(got) | set(want):
            if path not in got or path not in want:
                bad += 1
            elif any(got[path].get(k) != v for k, v in want[path].entry().items()):
                bad += 1
        return bad


def parse_manifest(blocks: np.ndarray) -> dict:
    """The manifest as stored in the volume's first blocks: its length as
    a little-endian int64, then that many bytes of JSON; ``{}`` where the
    length is out of range."""
    raw = np.asarray(blocks, np.uint8).reshape(-1)
    size = int(raw[:8].view("<i8")[0])
    if size <= 0 or size > raw.size - 8:
        return {}
    return json.loads(raw[8:8 + size].tobytes())
