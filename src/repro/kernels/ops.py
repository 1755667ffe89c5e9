"""Public jit'd entry points for the kernels package.

Every codec op takes ``use_pallas``/``interpret``; left unset, both follow
the backend (:mod:`repro.kernels.backend`), resolved once here:

* on a TPU -> the compiled Pallas kernel (``interpret=False``);
* elsewhere -> the pure-jnp oracle (``use_pallas=False``; CPU datapath,
  autodiff-safe), or with an explicit ``use_pallas=True`` the Pallas kernel
  body in interpret mode (tests).

An explicit ``interpret=True`` on a TPU raises.  Byte-level helpers convert
between uint8 chunk buffers and the int32-packed lanes the kernels consume.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gf
from repro.kernels import ref
from repro.kernels.backend import LANES, codec_mode
from repro.kernels.gf256_matmul import gf256_matmul, gf256_matmul_batch
from repro.kernels.parity_xor import parity_xor, parity_xor_batch
from repro.kernels.ssd_scan import ssd_scan
from repro.obs.hostspans import spanned


@functools.lru_cache(maxsize=None)
def rs_parity_coeff(k: int, m: int) -> jax.Array:
    """Device-resident (m, k) RS parity matrix, cached per (k, m).

    The matrices are tiny but rebuilding + re-transferring them on every
    encode forces a host->device pack and a retrace; caching the packed
    int32 array makes repeat encodes hit the jit cache directly.
    """
    return jnp.asarray(gf.rs_parity_matrix(k, m), jnp.int32)


@functools.lru_cache(maxsize=None)
def rs_decode_coeff(k: int, m: int, surviving: tuple[int, ...]) -> jax.Array:
    """Device-resident (k, k) RS decode matrix, cached per survivor set."""
    return jnp.asarray(gf.rs_decode_matrix(k, m, surviving), jnp.int32)


def pack_bytes(data_u8: jax.Array) -> jax.Array:
    """(..., 4*n) uint8 -> (..., n) int32 little-endian lane packing."""
    assert data_u8.shape[-1] % 4 == 0
    return jax.lax.bitcast_convert_type(
        data_u8.reshape(*data_u8.shape[:-1], -1, 4), jnp.int32
    )


def unpack_bytes(data_i32: jax.Array) -> jax.Array:
    """(..., n) int32 -> (..., 4*n) uint8."""
    u8 = jax.lax.bitcast_convert_type(data_i32, jnp.uint8)
    return u8.reshape(*data_i32.shape[:-1], -1)


def _pad_lanes(x: jax.Array) -> tuple[jax.Array, int]:
    """Pad the lane dim up to a multiple of the TPU lane width."""
    n = x.shape[-1]
    pad = (-n) % LANES
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, n


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def xor_parity(
    chunks_i32: jax.Array,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """XOR parity of (k, n) int32 -> (n,) int32."""
    use_pallas, interpret = codec_mode(use_pallas, interpret)
    if use_pallas:
        padded, n = _pad_lanes(chunks_i32)
        return parity_xor(padded, interpret=interpret)[:n]
    return ref.parity_xor_ref(chunks_i32)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def rs_matmul(
    coeff_i32: jax.Array,
    chunks_i32: jax.Array,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GF(256) (m,k) x (k,n) -> (m,n) on int32-packed bytes."""
    use_pallas, interpret = codec_mode(use_pallas, interpret)
    if use_pallas:
        padded, n = _pad_lanes(chunks_i32)
        return gf256_matmul(coeff_i32, padded, interpret=interpret)[:, :n]
    return ref.gf256_matmul_ref(coeff_i32, chunks_i32)


def rs_encode(
    chunks_i32: jax.Array,
    m: int,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Encode (k, n) data chunks into (m, n) RS parity chunks."""
    k = chunks_i32.shape[0]
    coeff = rs_parity_coeff(k, m)
    return rs_matmul(coeff, chunks_i32, use_pallas=use_pallas, interpret=interpret)


def rs_decode(
    surviving_i32: jax.Array,
    surviving_rows: tuple[int, ...],
    k: int,
    m: int,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Reconstruct the k data chunks from any k surviving codeword rows."""
    dec = rs_decode_coeff(k, m, tuple(surviving_rows))
    return rs_matmul(dec, surviving_i32, use_pallas=use_pallas, interpret=interpret)


# ------------------------------------------------------- batched (group) ops

@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def xor_parity_batch(
    chunks_i32: jax.Array,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """XOR parity for a whole stripe group: (S, k, n) int32 -> (S, n) int32."""
    use_pallas, interpret = codec_mode(use_pallas, interpret)
    if use_pallas:
        padded, n = _pad_lanes(chunks_i32)
        return parity_xor_batch(padded, interpret=interpret)[:, :n]
    return ref.parity_xor_batch_ref(chunks_i32)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def rs_matmul_batch(
    coeff_i32: jax.Array,
    chunks_i32: jax.Array,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """GF(256) (m,k) x (S,k,n) -> (S,m,n) on int32-packed bytes."""
    use_pallas, interpret = codec_mode(use_pallas, interpret)
    if use_pallas:
        padded, n = _pad_lanes(chunks_i32)
        return gf256_matmul_batch(coeff_i32, padded, interpret=interpret)[:, :, :n]
    return ref.gf256_matmul_batch_ref(coeff_i32, chunks_i32)


def rs_encode_batch(
    chunks_i32: jax.Array,
    m: int,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Encode (S, k, n) stripes into (S, m, n) RS parity in one fused call."""
    k = chunks_i32.shape[1]
    coeff = rs_parity_coeff(k, m)
    return rs_matmul_batch(
        coeff, chunks_i32, use_pallas=use_pallas, interpret=interpret
    )


def rs_decode_batch(
    surviving_i32: jax.Array,
    surviving_rows: tuple[int, ...],
    k: int,
    m: int,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Reconstruct (S, k, n) data from (S, k, n) survivors sharing one role set."""
    dec = rs_decode_coeff(k, m, tuple(surviving_rows))
    return rs_matmul_batch(
        dec, surviving_i32, use_pallas=use_pallas, interpret=interpret
    )


# -------------------------------------------- device-resident (donated) ops
#
# Entry points for the zero-copy group datapath: the caller hands over a
# packed int32 device buffer it will never touch again (the staging arena's
# per-group gather), so the input buffer is donated to XLA and the dispatch
# returns immediately (JAX async dispatch).  The group committer materializes
# the result with one np.asarray at the commit sync point.
#
# Donation is best-effort: when the output shape differs from the input's
# (encode maps k rows to m), XLA reports the buffer as unusable at compile
# time.  That is expected -- the donation still pays off on the square decode
# matmuls -- so the advisory compile-time warning is silenced at the call
# sites (a module-level filter would not survive pytest's warning capture).

@contextlib.contextmanager
def quiet_donation():
    """Context silencing XLA's advisory unusable-donation compile warning."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable",
            category=UserWarning,
        )
        yield


@functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret"), donate_argnums=(0,)
)
def xor_parity_batch_device(
    chunks_i32: jax.Array,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Donating ``xor_parity_batch``: (S, k, n) int32 -> (S, n) int32."""
    use_pallas, interpret = codec_mode(use_pallas, interpret)
    if use_pallas:
        padded, n = _pad_lanes(chunks_i32)
        return parity_xor_batch(padded, interpret=interpret)[:, :n]
    return ref.parity_xor_batch_ref(chunks_i32)


@functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret"), donate_argnums=(1,)
)
def rs_matmul_batch_device(
    coeff_i32: jax.Array,
    chunks_i32: jax.Array,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Donating ``rs_matmul_batch``: coeff kept, stripe buffer donated."""
    use_pallas, interpret = codec_mode(use_pallas, interpret)
    if use_pallas:
        padded, n = _pad_lanes(chunks_i32)
        return gf256_matmul_batch(coeff_i32, padded, interpret=interpret)[:, :, :n]
    return ref.gf256_matmul_batch_ref(coeff_i32, chunks_i32)


def rs_encode_batch_device(
    chunks_i32: jax.Array,
    m: int,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Donating ``rs_encode_batch`` (cached coeff matrix, donated stripes)."""
    k = chunks_i32.shape[1]
    coeff = rs_parity_coeff(k, m)
    return rs_matmul_batch_device(
        coeff, chunks_i32, use_pallas=use_pallas, interpret=interpret
    )


def rs_decode_batch_device(
    surviving_i32: jax.Array,
    surviving_rows: tuple[int, ...],
    k: int,
    m: int,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Donating ``rs_decode_batch`` (cached decode matrix, donated survivors)."""
    dec = rs_decode_coeff(k, m, tuple(surviving_rows))
    return rs_matmul_batch_device(
        dec, surviving_i32, use_pallas=use_pallas, interpret=interpret
    )


@spanned("codec", "pack")
def pack_bytes_np(data_u8: np.ndarray) -> np.ndarray:
    """Host-side ``pack_bytes``: a free dtype view, no device dispatch.

    numpy's in-memory byte order equals ``jax.lax.bitcast_convert_type``'s
    lane packing, so viewing a C-contiguous uint8 buffer as int32 produces
    bit-identical lanes to :func:`pack_bytes` without entering the device."""
    assert data_u8.shape[-1] % 4 == 0
    data_u8 = np.ascontiguousarray(data_u8)
    return data_u8.view(np.int32)


@spanned("codec", "pack")
def unpack_bytes_np(data_i32: np.ndarray) -> np.ndarray:
    """Host-side ``unpack_bytes``: a free dtype view of an int32 buffer."""
    return np.ascontiguousarray(data_i32).view(np.uint8)


def ssd_chunk_scan(
    x, dt, a, b, c, h0=None, *, chunk: int = 128,
    use_pallas: Optional[bool] = None, interpret: Optional[bool] = None,
):
    """Mamba-2 SSD scan; see kernels/ssd_scan.py.  Returns (y, h_final)."""
    use_pallas, interpret = codec_mode(use_pallas, interpret)
    if use_pallas:
        return ssd_scan(x, dt, a, b, c, h0, chunk=chunk, interpret=interpret)
    return ref.ssd_scan_ref(x, dt, a, b, c, h0)
