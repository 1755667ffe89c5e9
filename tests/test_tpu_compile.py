"""Compile the codec kernels for a described v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts (an XOR ``lax.reduce``
has no Mosaic lowering, for one), so these tests hold the main path's
kernels to the real compiler at its real shapes: stripe groups of S=256 at
4 KiB chunks (n=1024 lanes) and 16 KiB chunks (n=4096, the checkpoint's
``chunk_blocks=4``); RAID-5 (3+1) XOR encode and decode; RAID-6 (2+2) RS
encode and RS decode for every survivor set; the single-stripe XOR that
``checkpoint/state_parity.py`` dispatches; and the single-stripe RS product
of a RAID-6 degraded read.  Each case compiles through the ``ops`` entry
point the codec calls and asserts a Pallas TPU kernel (``tpu_custom_call``)
in the compiled program, under the kernel's stable name (``name=`` on its
``pallas_call``), which a profile shows as the kernel's op.  Beside them,
the checksum layer's device CRC32C (plain XLA, one int8 bit-matrix
product) compiles at its smallest and largest row bucket of 4 KiB blocks
without writing the unpacked bits to a temporary buffer.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import itertools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gf
from repro.integrity import checksum
from repro.kernels import ops

S = 256
LANES = (1024, 4096)
COMPILED = {"use_pallas": True, "interpret": False}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc_mod
    from jax.experimental import topologies

    cc = cc_mod.compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside the tree
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _has_kernel(text: str, name: str) -> bool:
    """A Pallas TPU kernel whose instruction carries ``name``."""
    return re.search(
        rf"%{name}(\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text) is not None


@pytest.mark.parametrize("n", LANES)
def test_raid5_xor_encode_decode_compiles(one_chip, n):
    """k=3 XOR: the encode of 3 data chunks and the decode of 3 survivors
    are the same (S, 3, n) kernel."""
    text = _compiled_text(
        lambda x: ops.xor_parity_batch_device(x, **COMPILED), (S, 3, n),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in text
    assert _has_kernel(text, "xor_parity_batch")


@pytest.mark.parametrize("n", LANES)
def test_raid6_rs_encode_compiles(one_chip, n):
    coeff = jnp.asarray(gf.rs_parity_matrix(2, 2), jnp.int32)
    text = _compiled_text(
        lambda x: ops.rs_matmul_batch_device(coeff, x, **COMPILED), (S, 2, n),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in text
    assert _has_kernel(text, "gf256_matmul_batch")


@pytest.mark.parametrize("survivors", list(itertools.combinations(range(4), 2)))
@pytest.mark.parametrize("n", LANES)
def test_raid6_rs_decode_compiles(one_chip, n, survivors):
    dec = jnp.asarray(gf.rs_decode_matrix(2, 2, survivors), jnp.int32)
    text = _compiled_text(
        lambda x: ops.rs_matmul_batch_device(dec, x, **COMPILED), (S, 2, n),
        sharding=one_chip,
    )
    assert "tpu_custom_call" in text
    assert _has_kernel(text, "gf256_matmul_batch")


@pytest.mark.parametrize("n", LANES)
def test_state_parity_single_stripe_xor_compiles(one_chip, n):
    text = _compiled_text(
        lambda x: ops.xor_parity(x, **COMPILED), (3, n), sharding=one_chip
    )
    assert "tpu_custom_call" in text
    assert _has_kernel(text, "xor_parity")


@pytest.mark.parametrize("n", LANES)
def test_raid6_single_stripe_rs_decode_compiles(one_chip, n):
    """A RAID-6 degraded read decodes one stripe: the (2, 2) decode matrix
    of survivors 1 and 3 times their (2, n) chunks."""
    dec = jnp.asarray(gf.rs_decode_matrix(2, 2, (1, 3)), jnp.int32)
    text = _compiled_text(
        lambda x: ops.rs_matmul(dec, x, **COMPILED), (2, n), sharding=one_chip
    )
    assert "tpu_custom_call" in text
    assert _has_kernel(text, "gf256_matmul")


@pytest.mark.parametrize("rows", (checksum.R_MIN, checksum.R_TILE))
def test_crc32c_device_product_compiles(one_chip, rows):
    """The (rows, 1024) int32 words of 4 KiB blocks against the (32, 1024,
    32) int8 bit matrix: the bits (rows x 32 KiB) are unpacked into the
    product's operand, not into a temporary buffer of the program."""
    words = jax.ShapeDtypeStruct((rows, 1024), jnp.int32, sharding=one_chip)
    bmat = jax.ShapeDtypeStruct((32, 1024, 32), jnp.int8, sharding=one_chip)
    _, const = checksum._pos_tables(4096)
    compiled = checksum._crc_rows.lower(words, bmat, const=const).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 32 * 1024
