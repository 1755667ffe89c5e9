"""Vectorized CRC32C (Castagnoli) over fixed-size blocks.

The write path stages payloads in int32-packed arenas
(``core.array._StripeArena``), so the checksum primitive must digest a
whole ``(N, block_bytes)`` uint8 view in one numpy pass -- no per-block
Python loops, no byte-at-a-time state machine on the hot path.

CRC is GF(2)-affine in the message, which makes a *per-position table*
formulation possible: for a fixed block length ``L`` there is a table
``postable[pos][byte]`` (the raw CRC contribution of ``byte`` at
position ``pos`` in an otherwise-zero message) and a constant folding
the ``0xFFFFFFFF`` init/xorout through ``L`` zero bytes, such that

    crc(M) = const(L)  XOR  XOR_{pos} postable[pos, M[pos]]

The whole batch then reduces to one fancy-indexed gather plus an XOR
reduction -- a shape (map + reduce over independent lanes) that ports
directly to a Pallas kernel if the arenas ever move on-device.  Tables
are built once per distinct block length and cached (1 KiB per
position: 4 MiB for 4 KiB blocks).

The same linearity gives a device formulation (:func:`crc32c_device`):
with the message's bits laid out as a 0/1 vector,

    crc(M) = const(L)  XOR  (bits(M) . B  mod 2)

for a fixed ``(8*L, 32)`` 0/1 matrix ``B`` whose row ``8*pos + bit`` holds
the bits of ``postable[pos, 1 << bit]``.  A batch of rows is then one
integer matrix product on the TPU's matrix unit.  ``crc32c_many`` takes it
for batches of at least :data:`R_MIN` rows on a TPU, and the table walk
for the rest; both build on the same ``postable``, so every caller gets
the same bits whichever path ran.

The same primitive digests arbitrary-length byte strings through the
classic byte-loop (:func:`crc32c`) for header/footer metadata, and the
two agree: ``crc32c(block.tobytes()) == crc32c_many(block[None])[0]``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import on_tpu
from repro.obs.hostspans import host_span

__all__ = ["CRC_BYTES", "R_MIN", "R_TILE", "crc32c", "crc32c_device",
           "crc32c_many", "crc32c_pack", "verify_many"]

CRC_BYTES = 4  # stored checksum width (uint32, little-endian when packed)

_POLY = np.uint32(0x82F63B78)  # CRC-32C (Castagnoli), reflected


def _base_table() -> np.ndarray:
    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(t & 1, (t >> 1) ^ _POLY, t >> 1).astype(np.uint32)
    return t


_TABLE = _base_table()

# Per-length cache: block length -> (postable (L, 256) uint32, const uint32)
_POS_CACHE: dict[int, tuple[np.ndarray, int]] = {}

# Positions digested per gather chunk; bounds the (N, chunk) uint32
# scratch so huge batches never materialize an N*L temp.
_CHUNK = 1024

# Device path: batches of at least R_MIN rows go to the device on a TPU,
# padded to a power-of-two bucket of rows up to R_TILE; larger batches go
# as R_TILE-row tiles.  On a v5e host a device call costs about 1.3 ms at
# any size up to a tile and the table walk about 22 us a 4 KiB row: the
# two meet at 64 rows.  R_TILE is one RAID-5 or RAID-6 group codeword
# (256 stripes x 4 drives x 1 block), so a whole group needs no pad copy.
R_MIN = 64
R_TILE = 1024


def _pos_tables(length: int) -> tuple[np.ndarray, int]:
    cached = _POS_CACHE.get(length)
    if cached is not None:
        return cached
    post = np.empty((length, 256), dtype=np.uint32)
    post[length - 1] = _TABLE
    for pos in range(length - 2, -1, -1):
        s = post[pos + 1]
        post[pos] = (s >> 8) ^ _TABLE[s & 0xFF]
    # Fold init=0xFFFFFFFF through `length` zero bytes, plus the xorout.
    c = 0xFFFFFFFF
    for _ in range(length):
        c = (c >> 8) ^ int(_TABLE[c & 0xFF])
    const = c ^ 0xFFFFFFFF
    _POS_CACHE[length] = (post, const)
    return post, const


def crc32c(data: bytes | bytearray | memoryview | np.ndarray) -> int:
    """Scalar CRC32C of an arbitrary-length byte string."""
    buf = np.frombuffer(memoryview(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data.reshape(-1)
    crc = 0xFFFFFFFF
    for b in buf.tobytes():
        crc = (crc >> 8) ^ int(_TABLE[(crc ^ b) & 0xFF])
    return crc ^ 0xFFFFFFFF


def _crc32c_host(blocks: np.ndarray) -> np.ndarray:
    """The table walk: ``(N, L) uint8 -> (N,) uint32``."""
    n, length = blocks.shape
    if length == 0:
        return np.zeros(n, dtype=np.uint32)
    post, const = _pos_tables(length)
    acc = np.full(n, const, dtype=np.uint32)
    for start in range(0, length, _CHUNK):
        stop = min(start + _CHUNK, length)
        idx = np.arange(start, stop)
        # (N, chunk) gather of per-position contributions, XOR-reduced.
        acc ^= np.bitwise_xor.reduce(post[idx, blocks[:, start:stop]], axis=1)
    return acc


def _bit_matrix(length: int) -> np.ndarray:
    """``B`` as ``(32, W, 32)`` int8 for ``W = length // 4`` int32 words:
    ``[j, w]`` is the row of message bit ``32 * w + j`` (little-endian
    words), that is, of bit ``j % 8`` of byte ``4 * w + j // 8``."""
    post, _ = _pos_tables(length)
    cols = post[:, 1 << np.arange(8)]                  # (L, 8): one per bit
    bits = (cols[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    return np.ascontiguousarray(
        bits.reshape(length // 4, 32, 32).transpose(1, 0, 2)).astype(np.int8)


@functools.partial(jax.jit, static_argnames=("const",))
def _crc_rows(words: jax.Array, bmat: jax.Array, const: int) -> jax.Array:
    """``(T, W)`` int32 rows -> ``(T,)`` uint32 CRC32C: the bits unpacked
    as ``(T, 32, W)`` (the words stay the lanes), one int8 product with
    int32 counts over both bit axes, each count's parity packed into a
    bit.  Laid out so, the program XLA compiles for a v5e needs no
    temporary buffer for the bits."""
    u = jax.lax.bitcast_convert_type(words, jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((u[:, None, :] >> shifts[None, :, None]) & 1).astype(jnp.int8)
    counts = jax.lax.dot_general(bits, bmat, (((1, 2), (0, 1)), ((), ())),
                                 preferred_element_type=jnp.int32)
    parity = (counts & 1).astype(jnp.uint32) << shifts
    # the bits are disjoint: their sum is their OR
    return jnp.sum(parity, axis=1, dtype=jnp.uint32) ^ jnp.uint32(const)


def _buckets() -> list[int]:
    return [R_MIN << i for i in range((R_TILE // R_MIN).bit_length())]


@functools.lru_cache(maxsize=None)
def _device_tables(length: int) -> tuple[jax.Array, int]:
    """``B`` on the device for rows of ``length`` bytes, and ``const``; the
    first call for a length also compiles every bucket, so that later
    calls at any row count compile nothing."""
    _, const = _pos_tables(length)
    bmat = jax.device_put(_bit_matrix(length))
    for rows in _buckets():
        zeros = np.zeros((rows, length // 4), np.int32)
        _crc_rows(zeros, bmat, const).block_until_ready()
    return bmat, const


def _as_words(blocks: np.ndarray) -> np.ndarray:
    """Rows as ``(N, L // 4)`` int32 words over the same bytes (zero-copy
    for a contiguous uint8 batch or an int32-packed arena)."""
    u8 = np.ascontiguousarray(blocks).view(np.uint8).reshape(blocks.shape[0], -1)
    if u8.shape[1] % 4:
        raise ValueError(f"device CRC32C needs rows of a multiple of 4 bytes, "
                         f"not {u8.shape[1]}")
    return u8.view(np.int32)


def crc32c_device(blocks: np.ndarray) -> np.ndarray:
    """CRC32C of each row on the device: ``(N, L) -> (N,) uint32``, ``L`` a
    multiple of 4 bytes.

    Each tile of at most :data:`R_TILE` rows is copied once as int32 words
    (``jax.device_put``), padded with zero rows to its power-of-two bucket
    (at least :data:`R_MIN`); the tiles are issued back to back and their
    results brought back with one wait."""
    words = _as_words(blocks)
    n, width = words.shape
    if n == 0:
        return np.zeros(0, dtype=np.uint32)
    bmat, const = _device_tables(4 * width)
    outs = []
    for start in range(0, n, R_TILE):
        tile = words[start:start + R_TILE]
        rows = tile.shape[0]
        bucket = max(R_MIN, 1 << (rows - 1).bit_length())
        if bucket != rows:
            tile = np.concatenate(
                [tile, np.zeros((bucket - rows, width), np.int32)])
        outs.append(_crc_rows(jax.device_put(tile), bmat, const))
    return np.concatenate(jax.device_get(outs))[:n]


def crc32c_many(blocks: np.ndarray, *,
                device: Optional[bool] = None) -> np.ndarray:
    """CRC32C of each row: ``(N, L) uint8 -> (N,) uint32``.

    Accepts any 2-D array whose rows are the messages; int32-packed
    arena rows digest zero-copy via a uint8 view.  ``device`` unset: the
    device product (:func:`crc32c_device`) for at least :data:`R_MIN` rows
    of a multiple of 4 bytes on a TPU, the host table walk otherwise; the
    results are the same bits either way.  One ``checksum:crc32c`` span
    covers the call, keyed by the path (``crc32c_device`` or
    ``crc32c_host``) and ``(N, L)``.
    """
    if blocks.dtype != np.uint8:
        blocks = np.ascontiguousarray(blocks).view(np.uint8)
    if blocks.ndim != 2:
        blocks = blocks.reshape(blocks.shape[0], -1)
    n, length = blocks.shape
    if device is None:
        device = n >= R_MIN and length > 0 and length % 4 == 0 and on_tpu()
    op = "crc32c_device" if device else "crc32c_host"
    with host_span("checksum", "crc32c", op=op, shapes=((n, length),)):
        return crc32c_device(blocks) if device else _crc32c_host(blocks)


def crc32c_pack(crcs: np.ndarray) -> np.ndarray:
    """Pack ``(N,) uint32`` checksums as ``(N, 4)`` little-endian bytes."""
    return np.ascontiguousarray(crcs, dtype="<u4").view(np.uint8).reshape(-1, 4)


def verify_many(blocks: np.ndarray, crcs: np.ndarray) -> np.ndarray:
    """Boolean mask: ``True`` where row i's CRC32C matches ``crcs[i]``."""
    return crc32c_many(blocks) == np.asarray(crcs, dtype=np.uint32)
