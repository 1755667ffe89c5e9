"""The plain reference: a map from LBA to the block last written there, and
the checksum the configuration stores beside every block.

It imports nothing of the program.  Its contents come only from the
benchmark's own seeded stream: the prefill and every payload the window
submits, copied in as they are submitted.
"""
from __future__ import annotations

import numpy as np

CRC32C_POLY = 0x82F63B78  # Castagnoli, reflected (RFC 3720, appendix B.4)


def _crc_table() -> np.ndarray:
    table = np.zeros(256, np.uint32)
    for byte in range(256):
        c = byte
        for _ in range(8):
            c = (c >> 1) ^ CRC32C_POLY if c & 1 else c >> 1
        table[byte] = c
    return table


CRC_TABLE = _crc_table()


def crc32c_rows(rows: np.ndarray) -> np.ndarray:
    """CRC32C of each row of a ``(N, L)`` uint8 array: the byte-at-a-time
    table walk, run over all rows at once."""
    rows = np.asarray(rows, np.uint8).reshape(len(rows), -1)
    crc = np.full(rows.shape[0], 0xFFFFFFFF, np.uint32)
    for col in rows.T:
        crc = (crc >> np.uint32(8)) ^ CRC_TABLE[(crc ^ col) & np.uint32(0xFF)]
    return crc ^ np.uint32(0xFFFFFFFF)


class BlockReference:
    """Volume of ``n_blocks`` blocks of ``block_bytes`` bytes, all zero
    until written (an unwritten LBA of the array reads back as zeros)."""

    def __init__(self, n_blocks: int, block_bytes: int):
        self.blocks = np.zeros((n_blocks, block_bytes), np.uint8)

    def write(self, lba: int, data: np.ndarray) -> None:
        data = np.asarray(data, np.uint8).reshape(-1, self.blocks.shape[1])
        self.blocks[lba:lba + data.shape[0]] = data

    def mismatches(self, lba: int, n_blocks: int, got) -> int:
        """Blocks of the ``n_blocks`` read at ``lba`` that differ from the
        reference; a missing or misshapen answer counts every block."""
        want = self.blocks[lba:lba + n_blocks]
        if got is None or np.shape(got) != want.shape:
            return n_blocks
        return int(np.any(np.asarray(got) != want, axis=1).sum())
