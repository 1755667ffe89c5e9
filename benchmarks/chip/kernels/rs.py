"""Bytes moved by the GF(256) Reed-Solomon programs (``repro.kernels.ops``).

Arguments and results are int32-packed byte lanes; the coefficient matrix
is ``(m, k)`` int32.  A program reads its inputs once and writes its
result once, counted from the logical shapes.

* ``rs_matmul``: ``(m, k) x (k, n)`` -> ``(m, n)``, the single-stripe
  product (the degraded read's decode, with the ``(k, k)`` decode matrix);
* ``rs_matmul_batch`` and ``rs_matmul_batch_device``: ``(m, k) x
  (S, k, n)`` -> ``(S, m, n)``, a stripe group's product (the group
  commit's encode).
"""
WORD = 4  # bytes per int32 lane


def _single(shapes) -> int:
    (m, k), (k2, n) = shapes
    return WORD * (m * k + k2 * n + m * n)


def _batch(shapes) -> int:
    (m, k), (s, k2, n) = shapes
    return WORD * (m * k + s * k2 * n + s * m * n)


BYTES = {
    "rs_matmul": _single,
    "rs_matmul_batch": _batch,
    "rs_matmul_batch_device": _batch,
}
