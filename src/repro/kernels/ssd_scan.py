"""Pallas TPU kernel: chunked Mamba-2 SSD (state-space duality) scan.

The SSD recurrence  h_t = exp(dt_t*a) h_{t-1} + dt_t (b_t (x) x_t),
y_t = c_t . h_t  is the compute hot-spot of the mamba2/zamba2 architectures.
A naive scan is latency-bound (T sequential steps of rank-1 updates); the SSD
blocked form turns it into MXU work: the sequence is cut into chunks of Q
tokens, each chunk does three (Q,Q)/(Q,N)/(Q,P) matmuls (intra-chunk), and a
single (N,P) state carries between chunks.

TPU mapping: grid = (BH, T//Q) with both dims sequential (TPU grid order is
row-major), so the chunk axis iterates innermost and the inter-chunk state
lives in a VMEM scratch buffer that persists across grid steps -- the same
accumulator-carry pattern as Pallas flash attention.  All tiles are MXU
aligned for the production sizes (Q=128, P=64/128, N=64/128); decay masks are
built from 2-D iotas (TPU requires >=2-D iota).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, h):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)    # (q, p)
    dt = dt_ref[0].astype(jnp.float32)  # (q,)
    a = a_ref[0, 0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)    # (q, n)
    c = c_ref[0].astype(jnp.float32)    # (q, n)
    q = x.shape[0]

    la = dt * a                        # (q,) log-decay per step (<= 0)
    s = jnp.cumsum(la)                 # inclusive cumulative log-decay
    # Lower-triangular decay kernel L[t, j] = exp(s_t - s_j), t >= j.
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l_mat = jnp.where(row >= col, jnp.exp(s[:, None] - s[None, :]), 0.0)

    h_prev = h[...]                    # (n, p)
    # Intra-chunk: (L . (C B^T)) @ (dt * X)
    cbt = jnp.dot(c, b.T, preferred_element_type=jnp.float32)   # (q, q)
    y_intra = jnp.dot(l_mat * cbt, dt[:, None] * x,
                      preferred_element_type=jnp.float32)       # (q, p)
    # Inter-chunk: exp(s_t) * (C @ h_prev)
    y_inter = jnp.exp(s)[:, None] * jnp.dot(
        c, h_prev, preferred_element_type=jnp.float32)          # (q, p)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # State update: h = exp(s_last) h_prev + sum_j exp(s_last - s_j) dt_j b_j x_j
    w = dt * jnp.exp(s[-1] - s)        # (q,)
    h_new = jnp.exp(s[-1]) * h_prev + jnp.dot(
        b.T * w[None, :], x, preferred_element_type=jnp.float32)  # (n, p)
    h[...] = h_new
    hout_ref[0] = h_new.astype(hout_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret")
)
def ssd_scan(
    x: jax.Array,   # (bh, t, p)
    dt: jax.Array,  # (bh, t)
    a: jax.Array,   # (bh,)
    b: jax.Array,   # (bh, t, n)
    c: jax.Array,   # (bh, t, n)
    h0: jax.Array | None = None,  # (bh, n, p)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Blocked SSD scan; returns (y (bh,t,p) f32, h_final (bh,n,p) f32).

    ``interpret`` follows the backend (see :mod:`repro.kernels.backend`)."""
    bh, t, p = x.shape
    n = b.shape[-1]
    q = min(chunk, t)
    assert t % q == 0, (t, q)
    if h0 is None:
        h0 = jnp.zeros((bh, n, p), jnp.float32)
    grid = (bh, t // q)
    y, h_final = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, p), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, p), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dt, a[:, None], b, c, h0)
    return y, h_final
