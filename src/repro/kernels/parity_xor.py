"""Pallas TPU kernel: XOR parity over k data chunks.

RAID-4/5 parity (and the XOR half of RAID-6) is a pure bandwidth problem:
read k chunks, write one.  On TPU the chunk bytes are bitcast to int32 lanes
and XOR-reduced on the VPU.  The kernel tiles the chunk dimension into
VMEM-resident blocks of (k, BLOCK_N) so each grid step streams k*BLOCK_N*4
bytes HBM->VMEM, XORs in-register, and writes BLOCK_N*4 bytes back -- the
roofline is HBM bandwidth and the kernel is a single pass.

The reduction over the k rows is a static unrolled chain of XORs (k is
small and known at trace time): Mosaic has no lowering for an XOR
``lax.reduce``, and the unrolled chain is what it would emit anyway.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import lane_block, resolve_interpret

DEFAULT_BLOCK_N = 2048  # int32 lanes per grid step (8 KiB per input row)


def _xor_rows(x: jax.Array) -> jax.Array:
    """XOR the rows of a (k, bn) tile into one (1, bn) row."""
    acc = x[0:1]
    for i in range(1, x.shape[0]):
        acc = acc ^ x[i : i + 1]
    return acc


def _parity_xor_kernel(x_ref, o_ref):
    o_ref[...] = _xor_rows(x_ref[...])  # (k, bn) -> (1, bn)


def _parity_xor_batch_kernel(x_ref, o_ref):
    o_ref[...] = _xor_rows(x_ref[0])[None]  # (1, k, bn) -> (1, 1, bn)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def parity_xor_batch(
    data: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """XOR-reduce a whole stripe group: (S, k, n) int32 -> (S, n) int32.

    One ``pallas_call`` over a 2-D (stripe, lane-tile) grid replaces S
    per-stripe dispatches: grid step (i, j) streams stripe i's (k, bn) tile
    through VMEM exactly like the single-stripe kernel, so the HBM-bandwidth
    roofline is unchanged while the dispatch cost is paid once per group.
    ``interpret`` follows the backend (see :mod:`repro.kernels.backend`).
    """
    s, k, n = data.shape
    bn = lane_block(n, block_n)
    out = pl.pallas_call(
        _parity_xor_batch_kernel,
        grid=(s, n // bn),
        in_specs=[pl.BlockSpec((1, k, bn), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((s, 1, n), jnp.int32),
        interpret=resolve_interpret(interpret),
        name="xor_parity_batch",
    )(data)
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def parity_xor(
    data: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """XOR-reduce (k, n) int32 -> (n,) int32 via Pallas.

    ``n`` must be a multiple of 128 (TPU lane width); the lane block is the
    largest multiple of 128 that is at most ``block_n`` and divides n.
    ``interpret`` follows the backend (see :mod:`repro.kernels.backend`).
    """
    k, n = data.shape
    bn = lane_block(n, block_n)
    out = pl.pallas_call(
        _parity_xor_kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((k, bn), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=resolve_interpret(interpret),
        name="xor_parity",
    )(data)
    return out[0]
