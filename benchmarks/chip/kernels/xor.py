"""Bytes moved by the XOR parity programs (``repro.kernels.ops``).

Arguments and results are int32-packed byte lanes.  A program reads its
input once and writes its result once; the bytes it needs are those two,
counted from the logical shapes (the program's own padding of the lane
axis to 128 lanes is not work the algorithm needs).

* ``xor_parity``: ``(k, n)`` -> ``(n,)``, the single-stripe XOR (the
  degraded read's decode; the program also calls it for a one-stripe
  encode);
* ``xor_parity_batch`` and ``xor_parity_batch_device``: ``(S, k, n)`` ->
  ``(S, n)``, a stripe group's XOR (the group commit's encode).
"""
WORD = 4  # bytes per int32 lane


def _single(shapes) -> int:
    (k, n), = shapes
    return WORD * (k * n + n)


def _batch(shapes) -> int:
    (s, k, n), = shapes
    return WORD * (s * k * n + s * n)


BYTES = {
    "xor_parity": _single,
    "xor_parity_batch": _batch,
    "xor_parity_batch_device": _batch,
}
