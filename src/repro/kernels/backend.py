"""How the kernels follow the backend they run on.

The codec mode is derived from the platform, never chosen by a user flag:

* on a TPU the Pallas kernels compile for the chip (``interpret=False``);
* elsewhere callers default to the jnp reference (``use_pallas=False``), and
  a Pallas kernel that is asked for explicitly runs in interpret mode.

An explicit ``interpret=True`` on a TPU is refused: it would run the kernel
body in Python on the host while looking like the production path.
"""
from __future__ import annotations

from typing import Optional

import jax

LANES = 128  # TPU vector lane width (int32 lanes per vreg row)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret mode exactly where the backend is not a TPU."""
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas kernels compile for "
            "the chip here; leave interpret unset"
        )
    return bool(interpret)


def codec_mode(
    use_pallas: Optional[bool], interpret: Optional[bool]
) -> tuple[bool, bool]:
    """Resolve ``(use_pallas, interpret)``: unset, Pallas kernels compiled for
    the chip on a TPU and the jnp reference elsewhere."""
    use_pallas = on_tpu() if use_pallas is None else bool(use_pallas)
    return use_pallas, resolve_interpret(interpret)


def lane_block(n: int, block_n: int) -> int:
    """Largest multiple of 128 lanes that is at most ``block_n`` and divides
    ``n`` (a 3-block 12 KiB chunk has n = 3072 lanes: block 1536, not 2048)."""
    if n % LANES or n <= 0:
        raise ValueError(f"lane count {n} is not a positive multiple of {LANES}")
    bn = max(LANES, min(block_n, n) // LANES * LANES)
    while n % bn:
        bn -= LANES
    return bn
