"""Pallas TPU kernel: GF(256) matrix-multiply for Reed-Solomon coding.

Computes P = M (*) D where M is an (m, k) GF(256) coefficient matrix and D is
(k, n) data with 4 GF bytes packed per int32 lane.  Used for:

* RS encode (M = parity rows of the systematic generator, m small),
* RS decode / degraded read (M = rows of the inverted surviving submatrix).

TPU adaptation: GPU erasure coders use 256-byte log/exp gather tables in
shared memory; gathers are poison for the TPU VPU, so instead the kernel uses
a branchless SWAR double-and-add -- 8 static steps of shift/mask/xor per
coefficient, all (8,128)-shaped VPU ops, no table lookups.  The coefficient
matrix is tiny and is broadcast to every grid step; the data streams through
VMEM in (k, BLOCK_N) tiles.  Arithmetic intensity is ~8k VPU ops per 4k bytes,
so the kernel stays bandwidth-bound like the XOR kernel (within ~1.3x).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.gf import swar_gf_scale
from repro.kernels.backend import lane_block, resolve_interpret

DEFAULT_BLOCK_N = 2048


def _make_kernel(m: int, k: int):
    def kernel(coeff_ref, d_ref, o_ref):
        d = d_ref[...]  # (k, bn) int32
        coeff = coeff_ref[...]  # (m, k) int32
        for j in range(m):
            acc = jnp.zeros_like(d[0])
            for i in range(k):
                acc = acc ^ swar_gf_scale(d[i], coeff[j, i])
            o_ref[j, :] = acc

    return kernel


def _make_batch_kernel(m: int, k: int):
    def kernel(coeff_ref, d_ref, o_ref):
        d = d_ref[0]  # (k, bn) int32 -- one stripe's tile
        coeff = coeff_ref[...]  # (m, k) int32
        for j in range(m):
            acc = jnp.zeros_like(d[0])
            for i in range(k):
                acc = acc ^ swar_gf_scale(d[i], coeff[j, i])
            o_ref[0, j, :] = acc

    return kernel


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gf256_matmul_batch(
    coeff: jax.Array,
    data: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(m, k) GF coeffs x (S, k, n) packed int32 -> (S, m, n) packed int32.

    Batched variant for whole stripe groups: a 2-D (stripe, lane-tile) grid
    runs the same SWAR double-and-add body per tile, with the tiny coefficient
    matrix broadcast to every grid step, so one ``pallas_call`` encodes (or
    decodes) all S stripes instead of S dispatches.  ``interpret`` follows
    the backend (see :mod:`repro.kernels.backend`).
    """
    m, k = coeff.shape
    s, k2, n = data.shape
    assert k == k2, (coeff.shape, data.shape)
    bn = lane_block(n, block_n)
    return pl.pallas_call(
        _make_batch_kernel(m, k),
        grid=(s, n // bn),
        in_specs=[
            pl.BlockSpec((m, k), lambda i, j: (0, 0)),
            pl.BlockSpec((1, k, bn), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, m, bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((s, m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
        name="gf256_matmul_batch",
    )(coeff.astype(jnp.int32), data)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gf256_matmul(
    coeff: jax.Array,
    data: jax.Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(m, k) GF coeffs x (k, n) packed int32 -> (m, n) packed int32."""
    m, k = coeff.shape
    k2, n = data.shape
    assert k == k2, (coeff.shape, data.shape)
    bn = lane_block(n, block_n)
    return pl.pallas_call(
        _make_kernel(m, k),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((m, k), lambda i: (0, 0)),
            pl.BlockSpec((k, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
        name="gf256_matmul",
    )(coeff.astype(jnp.int32), data)
