"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp/table oracles,
swept over shapes and dtypes, plus hypothesis property tests on GF(256)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hyp import given, settings, st

from repro.core import gf
from repro.kernels import ops, ref


# ---------------------------------------------------------------- GF field

@given(st.integers(0, 255), st.integers(0, 255), st.integers(0, 255))
@settings(max_examples=200, deadline=None)
def test_gf_field_axioms(a, b, c):
    m = gf.gf_mul
    assert m(a, b) == m(b, a)
    assert m(a, m(b, c)) == m(m(a, b), c)
    assert m(a, b ^ c) == m(a, b) ^ m(a, c)  # distributes over XOR
    if a:
        assert m(a, gf.gf_inv(a)) == 1


@given(st.integers(1, 12), st.integers(1, 4))
@settings(max_examples=30, deadline=None)
def test_rs_generator_is_mds(k, m):
    """Every k x k submatrix of the systematic generator is invertible."""
    import itertools

    gen = gf.rs_encode_matrix(k, m)
    rows = list(range(k + m))
    count = 0
    for sub in itertools.combinations(rows, k):
        gf.gf_inv_matrix_np(gen[list(sub)])  # raises if singular
        count += 1
        if count > 20:
            break


@given(
    st.integers(0, 2**31 - 1),
    st.integers(0, 255),
)
@settings(max_examples=100, deadline=None)
def test_swar_gf_scale_matches_tables(word, coeff):
    packed = np.array([word], dtype=np.int32)
    got = gf.swar_gf_scale(packed, coeff)
    want_bytes = gf.gf_mul_np(
        packed.view(np.uint8), np.full(4, coeff, np.uint8)
    )
    assert np.array_equal(np.asarray(got, np.int32).view(np.uint8), want_bytes)


# ------------------------------------------------------------ parity kernels

@pytest.mark.parametrize("k", [2, 3, 5, 8])
@pytest.mark.parametrize("n", [128, 1024, 4096])
def test_parity_xor_shapes(k, n):
    rng = np.random.default_rng(k * n)
    x = jnp.asarray(rng.integers(-(2**31), 2**31, (k, n), dtype=np.int64), jnp.int32)
    got = ops.xor_parity(x, use_pallas=True, interpret=True)
    want = ref.parity_xor_ref(x)
    assert jnp.array_equal(got, want)
    assert np.array_equal(
        np.asarray(got), np.bitwise_xor.reduce(np.asarray(x), axis=0)
    )


@pytest.mark.parametrize("k", range(1, 8))
@pytest.mark.parametrize("n", [1024, 3072, 4096])
def test_unrolled_xor_kernels_match_ref(k, n):
    """The unrolled-XOR kernels, single-stripe and batched, are bit-identical
    to the oracle; n=3072 (a 12 KiB chunk) takes a 1536-lane block, the
    largest multiple of 128 that divides it within the 2048 default."""
    from repro.kernels.backend import lane_block
    from repro.kernels.parity_xor import parity_xor, parity_xor_batch

    rng = np.random.default_rng(k * n)
    x = jnp.asarray(
        rng.integers(-(2**31), 2**31, (4, k, n), dtype=np.int64), jnp.int32
    )
    assert jnp.array_equal(parity_xor(x[0], interpret=True),
                           ref.parity_xor_ref(x[0]))
    assert jnp.array_equal(parity_xor_batch(x, interpret=True),
                           ref.parity_xor_batch_ref(x))
    bn = lane_block(n, 2048)
    assert bn == {1024: 1024, 3072: 1536, 4096: 2048}[n]


@pytest.mark.parametrize("tpu", [False, True])
def test_codec_mode_follows_backend(monkeypatch, tpu):
    """Unset, the codec runs compiled Pallas on a TPU and the jnp reference
    elsewhere; an explicit interpret=True on a TPU is refused."""
    from repro.core.raid import StripeCodec, make_scheme
    from repro.kernels import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: tpu)
    codec = StripeCodec(make_scheme("raid5", 4))
    assert (codec.use_pallas, codec.interpret) == (tpu, not tpu)
    assert backend.resolve_interpret(False) is False
    if tpu:
        with pytest.raises(ValueError, match="TPU"):
            StripeCodec(make_scheme("raid5", 4), use_pallas=True, interpret=True)
    else:
        assert backend.resolve_interpret(True) is True


def test_parity_xor_unaligned_lanes():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2**31, (3, 20), dtype=np.int64), jnp.int32)
    got = ops.xor_parity(x, use_pallas=True, interpret=True)
    assert np.array_equal(np.asarray(got), np.bitwise_xor.reduce(np.asarray(x), 0))


@pytest.mark.parametrize("k,m", [(2, 1), (3, 1), (3, 2), (6, 2), (4, 3)])
@pytest.mark.parametrize("n_bytes", [512, 4096])
def test_gf256_matmul_vs_table_oracle(k, m, n_bytes):
    rng = np.random.default_rng(k * 7 + m)
    data = rng.integers(0, 256, (k, n_bytes), dtype=np.uint8)
    coeff = gf.rs_parity_matrix(k, m)
    want = gf.gf_matmul_np(coeff, data)
    packed = ops.pack_bytes(jnp.asarray(data))
    got = ops.rs_matmul(
        jnp.asarray(coeff, jnp.int32), packed, use_pallas=True, interpret=True
    )
    assert np.array_equal(np.asarray(ops.unpack_bytes(got)), want)


@given(
    st.integers(2, 6),  # k
    st.integers(1, 2),  # m
    st.randoms(use_true_random=False),
)
@settings(max_examples=25, deadline=None)
def test_rs_roundtrip_any_survivors(k, m, rnd):
    rng = np.random.default_rng(rnd.randint(0, 1 << 30))
    data = rng.integers(0, 256, (k, 256), dtype=np.uint8)
    packed = ops.pack_bytes(jnp.asarray(data))
    parity = ops.rs_encode(packed, m, use_pallas=True, interpret=True)
    code = jnp.concatenate([packed, parity], axis=0)
    all_rows = list(range(k + m))
    rnd.shuffle(all_rows)
    surv = tuple(sorted(all_rows[:k]))
    rec = ops.rs_decode(code[np.array(surv)], surv, k, m,
                        use_pallas=True, interpret=True)
    assert np.array_equal(np.asarray(ops.unpack_bytes(rec)), data)


# ------------------------------------------------------------------- SSD

@pytest.mark.parametrize("t,chunk", [(64, 16), (128, 128), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_vs_ref(t, chunk, dtype):
    rng = np.random.default_rng(t + chunk)
    bh, p, n = 3, 8, 16
    x = jnp.asarray(rng.standard_normal((bh, t, p)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (bh, t)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (bh,)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((bh, t, n)), dtype)
    c = jnp.asarray(rng.standard_normal((bh, t, n)), dtype)
    y0, h0 = ref.ssd_scan_ref(x, dt, a, b, c)
    y1, h1 = ops.ssd_chunk_scan(x, dt, a, b, c, chunk=chunk,
                                use_pallas=True, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), atol=tol, rtol=tol)


def test_ssd_scan_state_continuation():
    """Scanning [first half] then [second half with carried state] must match
    one full scan -- the decode-from-prefill invariant."""
    rng = np.random.default_rng(5)
    bh, t, p, n = 2, 128, 4, 8
    x = jnp.asarray(rng.standard_normal((bh, t, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (bh, t)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (bh,)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((bh, t, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bh, t, n)), jnp.float32)
    y_full, h_full = ops.ssd_chunk_scan(x, dt, a, b, c, chunk=32,
                                        use_pallas=True)
    half = t // 2
    y1, h1 = ops.ssd_chunk_scan(x[:, :half], dt[:, :half], a, b[:, :half],
                                c[:, :half], chunk=32, use_pallas=True)
    y2, h2 = ops.ssd_chunk_scan(x[:, half:], dt[:, half:], a, b[:, half:],
                                c[:, half:], h1, chunk=32, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_full[:, half:]), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2),
                               atol=1e-4, rtol=1e-4)


def test_chunked_jnp_ssd_matches_ref():
    from repro.models.mamba2 import ssd_chunked

    rng = np.random.default_rng(11)
    bsz, t, h, p, n = 2, 96, 4, 8, 16
    x = jnp.asarray(rng.standard_normal((bsz, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (bsz, t, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((bsz, t, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, t, n)), jnp.float32)
    y, hf = ssd_chunked(x, dt, a, b, c, chunk=32)
    # reference: per (batch,head) sequential scan with shared b/c
    xr = x.transpose(0, 2, 1, 3).reshape(bsz * h, t, p)
    dtr = dt.transpose(0, 2, 1).reshape(bsz * h, t)
    ar = jnp.tile(a, bsz)
    br = jnp.repeat(b, h, axis=0)
    cr = jnp.repeat(c, h, axis=0)
    y_ref, h_ref = ref.ssd_scan_ref(xr, dtr, ar, br, cr)
    y_ref = y_ref.reshape(bsz, h, t, p).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-4, rtol=2e-4)
