"""RAID schemes and the stripe codec (encode / decode / placement rotation).

Supports the paper's five schemes (Exp#4): RAID-0, RAID-01, RAID-4, RAID-5,
RAID-6 on an n-drive array.  The codec operates on int32-packed chunk
payloads and dispatches to the Pallas kernels (XOR for single parity, GF(256)
Reed-Solomon for double parity) or their jnp oracles.

Placement: role r of a stripe lives on drive ``(r + rot) % n`` where
``rot = stripe_seq % n`` for rotating schemes (RAID-5/6) and ``rot = 0`` for
fixed-parity schemes (RAID-0/01/4) -- the classic left-symmetric rotation the
paper sketches in Figure 3.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import gf
from repro.kernels import ops
from repro.kernels.backend import codec_mode
from repro.obs import hostspans
from repro.obs.hostspans import host_span, spanned


@dataclasses.dataclass(frozen=True)
class RaidScheme:
    name: str
    k: int  # data chunks per stripe
    m: int  # parity chunks per stripe
    rotate: bool  # rotate parity placement across drives
    mirror: bool = False  # RAID-01: parity chunks are copies of data chunks

    @property
    def n(self) -> int:
        return self.k + self.m

    def rotation(self, stripe_seq: int) -> int:
        return stripe_seq % self.n if self.rotate else 0

    def role_to_drive(self, role: int, stripe_seq: int) -> int:
        return (role + self.rotation(stripe_seq)) % self.n

    def drive_to_role(self, drive: int, stripe_seq: int) -> int:
        return (drive - self.rotation(stripe_seq)) % self.n

    def rotation_many(self, stripe_seqs: np.ndarray) -> np.ndarray:
        """Vectorized :meth:`rotation` (batched commit/harvest paths)."""
        seqs = np.asarray(stripe_seqs, dtype=np.int64)
        return seqs % self.n if self.rotate else np.zeros(seqs.shape, np.int64)

    def drive_to_role_many(self, drive: int, stripe_seqs: np.ndarray) -> np.ndarray:
        """Vectorized :meth:`drive_to_role` for one drive across stripes."""
        return (drive - self.rotation_many(stripe_seqs)) % self.n


def make_scheme(name: str, n_drives: int) -> RaidScheme:
    name = name.lower()
    if name == "raid0":
        return RaidScheme("raid0", n_drives, 0, rotate=False)
    if name == "raid01":
        if n_drives % 2:
            raise ValueError("raid01 needs an even drive count")
        return RaidScheme("raid01", n_drives // 2, n_drives // 2, rotate=False, mirror=True)
    if name == "raid4":
        return RaidScheme("raid4", n_drives - 1, 1, rotate=False)
    if name == "raid5":
        return RaidScheme("raid5", n_drives - 1, 1, rotate=True)
    if name == "raid6":
        return RaidScheme("raid6", n_drives - 2, 2, rotate=True)
    raise ValueError(f"unknown RAID scheme {name!r}")


class StripeCodec:
    """Encode/decode stripes for a scheme, via Pallas kernels or oracles.

    Two byte-level surfaces exist side by side:

    * ``encode_np``/``decode_np`` and their ``_batch`` variants -- blocking
      uint8-in/uint8-out convenience wrappers (host packing is a free dtype
      view; one device round trip per call).  A single-parity ``decode_np``
      that lost a data role brings back only the rebuilt row and puts the
      stripe's rows together on the host;
    * ``encode_batch_async``/``decode_batch_async`` -- the device-resident
      group datapath: take an int32-packed host buffer the caller gives up
      (an arena gather), donate it to XLA, and return the *un-materialized*
      device array so the dispatch overlaps host-side commit work.  The
      caller syncs with :meth:`materialize`.

    ``copy_stats`` (optional) is an object with ``h2d_copies/h2d_bytes/
    d2h_copies/d2h_bytes`` counters (e.g. :class:`repro.core.array.Stats`)
    bumped on every host<->device transfer the codec performs.

    The codec mode is resolved once, here, from the backend when left unset
    (:mod:`repro.kernels.backend`): compiled Pallas kernels on a TPU, the
    jnp reference elsewhere.

    Host spans (:mod:`repro.obs.hostspans`): ``codec:h2d`` (the copy to the
    device), ``codec:issue`` (issuing an encode's or a decode's programs,
    counted by method and argument shapes), ``codec:wait`` (the host
    blocked on the result) and ``codec:d2h`` (the copy back).
    """

    def __init__(
        self,
        scheme: RaidScheme,
        *,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
    ):
        self.scheme = scheme
        self.use_pallas, self.interpret = codec_mode(use_pallas, interpret)
        self.copy_stats = None

    # -- host<->device accounting -------------------------------------------

    @spanned("codec", "h2d")
    def _to_device(self, packed_np: np.ndarray) -> jnp.ndarray:
        if self.copy_stats is not None:
            self.copy_stats.h2d_copies += 1
            self.copy_stats.h2d_bytes += packed_np.nbytes
        # jnp.array (copy=True), NOT jnp.asarray: on the CPU backend asarray
        # zero-copies, and donating a device buffer that aliases host memory
        # the caller still reads (the arena gather doubles as the commit
        # payload) would let XLA scribble over it.
        return jnp.array(packed_np)

    def materialize(self, out_dev: jnp.ndarray) -> np.ndarray:
        """Sync point: block on the device result and bring it to the host."""
        if hostspans.current() is not None:
            # recording: wait apart from the copy, to time each.  Not
            # otherwise: a second blocking call costs a thread wake-up
            # whenever the result is not ready yet
            with host_span("codec", "wait"):
                out_dev.block_until_ready()
        with host_span("codec", "d2h"):
            out = np.asarray(out_dev)
        if self.copy_stats is not None:
            self.copy_stats.d2h_copies += 1
            self.copy_stats.d2h_bytes += out.nbytes
        return out

    # data: (k, n_i32) int32 packed chunk payloads
    @spanned("codec", "issue", dispatch=True)
    def encode(self, data_i32: jnp.ndarray) -> jnp.ndarray:
        """Return (m, n_i32) parity chunks (empty for RAID-0)."""
        s = self.scheme
        assert data_i32.shape[0] == s.k, (data_i32.shape, s)
        if s.m == 0:
            return jnp.zeros((0, data_i32.shape[1]), jnp.int32)
        if s.mirror:
            return data_i32
        if s.m == 1:
            p = ops.xor_parity(
                data_i32, use_pallas=self.use_pallas, interpret=self.interpret
            )
            return p[None, :]
        return ops.rs_encode(
            data_i32, s.m, use_pallas=self.use_pallas, interpret=self.interpret
        )

    @spanned("codec", "issue", dispatch=True)
    def decode(
        self, surviving_i32: jnp.ndarray, surviving_roles: tuple[int, ...]
    ) -> jnp.ndarray:
        """Reconstruct the data chunks from k surviving codeword rows.

        Returns the k data rows, (k, n), except where a single-parity stripe
        lost a data role: then only that row, (n,), the XOR of the survivors.
        The other data rows are among the survivors already, and
        :meth:`decode_np` puts them together on the host."""
        s = self.scheme
        if s.m == 0:
            raise ValueError("RAID-0 cannot decode lost chunks")
        if s.mirror:
            # role r and role r+k are copies; pick whichever survived.
            out = {}
            for row, role in zip(surviving_i32, surviving_roles):
                out.setdefault(role % s.k, row)
            if len(out) < s.k:
                raise ValueError("RAID-01: both copies of a chunk lost")
            return jnp.stack([out[i] for i in range(s.k)], axis=0)
        roles = tuple(surviving_roles)
        if len(roles) != s.k:
            raise ValueError(f"need exactly k={s.k} surviving rows, got {len(roles)}")
        if set(roles) == set(range(s.k)):
            # all data roles survive (possibly permuted): just reorder.
            order = [roles.index(i) for i in range(s.k)]
            return surviving_i32[jnp.array(order)]
        if s.m == 1:
            # Single parity: the lost data chunk is the XOR of the survivors.
            assert len(set(range(s.k)) - set(roles)) == 1
            return ops.xor_parity(
                surviving_i32, use_pallas=self.use_pallas, interpret=self.interpret
            )
        return ops.rs_decode(
            surviving_i32, roles, s.k, s.m,
            use_pallas=self.use_pallas, interpret=self.interpret,
        )

    # batched (stripe-group) datapath: data (S, k, n_i32) int32
    @spanned("codec", "issue", dispatch=True)
    def encode_batch(self, data_i32: jnp.ndarray) -> jnp.ndarray:
        """Encode S stripes at once: (S, k, n) -> (S, m, n) parity.

        One fused kernel dispatch per group instead of one per stripe; the
        output is bit-identical to stacking ``encode`` over the S stripes.
        """
        s = self.scheme
        assert data_i32.ndim == 3 and data_i32.shape[1] == s.k, (data_i32.shape, s)
        if s.m == 0:
            return jnp.zeros((data_i32.shape[0], 0, data_i32.shape[2]), jnp.int32)
        if s.mirror:
            return data_i32
        if s.m == 1:
            p = ops.xor_parity_batch(
                data_i32, use_pallas=self.use_pallas, interpret=self.interpret
            )
            return p[:, None, :]
        return ops.rs_encode_batch(
            data_i32, s.m, use_pallas=self.use_pallas, interpret=self.interpret
        )

    @spanned("codec", "issue", dispatch=True)
    def decode_batch(
        self, surviving_i32: jnp.ndarray, surviving_roles: tuple[int, ...]
    ) -> jnp.ndarray:
        """Reconstruct S stripes' data chunks from survivors sharing one role
        set: (S, k, n) survivors -> (S, k, n) data, bit-identical to stacking
        :meth:`decode_np` over the S stripes."""
        s = self.scheme
        if s.m == 0:
            raise ValueError("RAID-0 cannot decode lost chunks")
        roles = tuple(surviving_roles)
        if s.mirror:
            out = {}
            for i, role in enumerate(roles):
                out.setdefault(role % s.k, surviving_i32[:, i])
            if len(out) < s.k:
                raise ValueError("RAID-01: both copies of a chunk lost")
            return jnp.stack([out[i] for i in range(s.k)], axis=1)
        if len(roles) != s.k:
            raise ValueError(f"need exactly k={s.k} surviving rows, got {len(roles)}")
        if set(roles) == set(range(s.k)):
            order = [roles.index(i) for i in range(s.k)]
            return surviving_i32[:, jnp.array(order)]
        if s.m == 1:
            lost = set(range(s.k)) - set(roles)
            assert len(lost) == 1
            lost_role = lost.pop()
            rec = ops.xor_parity_batch(
                surviving_i32, use_pallas=self.use_pallas, interpret=self.interpret
            )
            cols = {role: surviving_i32[:, i] for i, role in enumerate(roles) if role < s.k}
            cols[lost_role] = rec
            return jnp.stack([cols[i] for i in range(s.k)], axis=1)
        return ops.rs_decode_batch(
            surviving_i32, roles, s.k, s.m,
            use_pallas=self.use_pallas, interpret=self.interpret,
        )

    def decode_np(self, surviving: np.ndarray, surviving_roles: tuple[int, ...]) -> np.ndarray:
        """Byte-level convenience wrapper (uint8 in/out) used by recovery paths.

        One device round trip per call.  For single parity with a lost data
        role the device computes only that row (the XOR of the survivors)
        and only it comes back; the k rows are put together here on the
        host, the others taken from ``surviving``."""
        roles = tuple(surviving_roles)
        packed = self._to_device(ops.pack_bytes_np(surviving))
        out = self.materialize(self.decode(packed, roles))
        if out.ndim == 2:
            return ops.unpack_bytes_np(out)
        # one rebuilt data row: every other data row survived
        k = self.scheme.k
        data = np.empty((k, surviving.shape[1]), np.uint8)
        rebuilt = np.ones(k, bool)
        for row, role in zip(surviving, roles):
            if role < k:
                data[role] = row
                rebuilt[role] = False
        data[rebuilt] = ops.unpack_bytes_np(out)
        return data

    def encode_np(self, data: np.ndarray) -> np.ndarray:
        if not self.scheme.m:
            return np.zeros((0, data.shape[1]), np.uint8)
        packed = self._to_device(ops.pack_bytes_np(data))
        out = self.encode(packed)
        return ops.unpack_bytes_np(self.materialize(out)).reshape(self.scheme.m, -1)

    @staticmethod
    def _pad_batch(data: np.ndarray) -> tuple[np.ndarray, int]:
        """Pad the stripe dim to the next power of two (zero stripes).

        Partial groups (flush, segment tail) would otherwise compile a fresh
        XLA executable per distinct S; bucketing to powers of two bounds the
        shape universe at log2(G) variants so steady state never recompiles.
        Zero padding is exact: every scheme's codec is stripe-independent.
        """
        s_count = data.shape[0]
        target = 1 << max(0, (s_count - 1).bit_length())
        if target != s_count:
            data = np.concatenate(
                [data, np.zeros((target - s_count, *data.shape[1:]), data.dtype)]
            )
        return data, s_count

    def encode_batch_np(self, data: np.ndarray) -> np.ndarray:
        """(S, k, n_bytes) uint8 -> (S, m, n_bytes) parity, one pack/unpack
        round-trip and one fused kernel call for the whole batch."""
        s_count, _, n_bytes = data.shape
        if self.scheme.m == 0:
            return np.zeros((s_count, 0, n_bytes), np.uint8)
        out_dev = self.encode_batch_async(
            ops.pack_bytes_np(self._pad_batch(np.ascontiguousarray(data))[0])
        )
        return ops.unpack_bytes_np(self.materialize(out_dev))[:s_count]

    def decode_batch_np(
        self, surviving: np.ndarray, surviving_roles: tuple[int, ...]
    ) -> np.ndarray:
        """(S, k, n_bytes) uint8 survivors -> (S, k, n_bytes) data."""
        s_count = surviving.shape[0]
        out_dev = self.decode_batch_async(
            ops.pack_bytes_np(self._pad_batch(np.ascontiguousarray(surviving))[0]),
            surviving_roles,
        )
        return ops.unpack_bytes_np(self.materialize(out_dev))[:s_count]

    # -- device-resident group entry points (donated buffers, async) ---------

    @spanned("codec", "issue", dispatch=True)
    def encode_batch_async(self, packed_np: np.ndarray) -> jnp.ndarray:
        """Dispatch a fused group encode and return the device array.

        ``packed_np`` is an int32-packed (S, k, n_i32) host buffer the caller
        relinquishes (typically a fresh arena gather, already power-of-two
        bucketed); it is copied to the device once and the device buffer is
        *donated* to the kernel, so steady-state group commits reuse the same
        allocation instead of growing a fresh one per group.  The returned
        array is not materialized -- JAX async dispatch lets the encode run
        while the caller commits the previous group; sync via
        :meth:`materialize`."""
        s = self.scheme
        assert packed_np.ndim == 3 and packed_np.shape[1] == s.k, packed_np.shape
        packed = self._to_device(packed_np)
        if s.m == 0:
            return jnp.zeros((packed.shape[0], 0, packed.shape[2]), jnp.int32)
        if s.mirror:
            return packed
        with ops.quiet_donation():
            if s.m == 1:
                p = ops.xor_parity_batch_device(
                    packed, use_pallas=self.use_pallas, interpret=self.interpret
                )
                return p[:, None, :]
            return ops.rs_encode_batch_device(
                packed, s.m, use_pallas=self.use_pallas, interpret=self.interpret
            )

    @spanned("codec", "issue", dispatch=True)
    def decode_batch_async(
        self, packed_np: np.ndarray, surviving_roles: tuple[int, ...]
    ) -> jnp.ndarray:
        """Donating, async variant of :meth:`decode_batch` (see above)."""
        s = self.scheme
        roles = tuple(surviving_roles)
        if s.m == 0:
            raise ValueError("RAID-0 cannot decode lost chunks")
        packed = self._to_device(packed_np)
        if s.mirror:
            return self.decode_batch(packed, roles)
        if len(roles) != s.k:
            raise ValueError(f"need exactly k={s.k} surviving rows, got {len(roles)}")
        if set(roles) == set(range(s.k)):
            order = [roles.index(i) for i in range(s.k)]
            return packed[:, jnp.array(order)]
        with ops.quiet_donation():
            if s.m == 1:
                lost = set(range(s.k)) - set(roles)
                lost_role = lost.pop()
                # slice the survivor columns out *before* the donating call:
                # the donated buffer is dead the moment the kernel takes it
                cols = {
                    role: packed[:, i] for i, role in enumerate(roles) if role < s.k
                }
                cols[lost_role] = ops.xor_parity_batch_device(
                    packed, use_pallas=self.use_pallas, interpret=self.interpret
                )
                return jnp.stack([cols[i] for i in range(s.k)], axis=1)
            return ops.rs_decode_batch_device(
                packed, roles, s.k, s.m,
                use_pallas=self.use_pallas, interpret=self.interpret,
            )


def _meta_rows(lbas: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """(rows, c) u64 LBAs + (rows, c) u64 timestamps -> (rows, 16c) bytes."""
    rows = lbas.shape[0]
    return np.concatenate(
        [
            np.ascontiguousarray(lbas.astype(np.uint64)).view(np.uint8).reshape(rows, -1),
            np.ascontiguousarray(ts.astype(np.uint64)).view(np.uint8).reshape(rows, -1),
        ],
        axis=1,
    )


def _meta_unrows(raw: np.ndarray, c: int) -> tuple[np.ndarray, np.ndarray]:
    rows = raw.shape[0]
    lbas = np.ascontiguousarray(raw[:, : 8 * c]).view(np.uint64).reshape(rows, c)
    ts = np.ascontiguousarray(raw[:, 8 * c :]).view(np.uint64).reshape(rows, c)
    return lbas, ts


@spanned("codec", "meta")
def parity_oob(
    codec: "StripeCodec", data_lbas: np.ndarray, data_ts: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Paper §3.1: parity blocks carry parity-based redundancy of the data
    blocks' LBAs and timestamps (the stripe id is replicated separately).

    We encode the metadata with the *same* erasure code as the payload, so
    metadata survives exactly the failures the payload survives (XOR for
    m=1, RS for m=2, copies for mirrors)."""
    c = data_lbas.shape[1]
    rows = _meta_rows(data_lbas, data_ts)
    enc = codec.encode_np(rows)
    return _meta_unrows(enc, c)


def _meta_rows_batch(lbas: np.ndarray, ts: np.ndarray) -> np.ndarray:
    """(S, rows, c) u64 LBAs + timestamps -> (S, rows, 16c) bytes."""
    s, rows, c = lbas.shape
    return np.concatenate(
        [
            np.ascontiguousarray(lbas.astype(np.uint64)).view(np.uint8).reshape(s, rows, -1),
            np.ascontiguousarray(ts.astype(np.uint64)).view(np.uint8).reshape(s, rows, -1),
        ],
        axis=2,
    )


def _meta_unrows_batch(raw: np.ndarray, c: int) -> tuple[np.ndarray, np.ndarray]:
    s, rows = raw.shape[0], raw.shape[1]
    lbas = np.ascontiguousarray(raw[:, :, : 8 * c]).view(np.uint64).reshape(s, rows, c)
    ts = np.ascontiguousarray(raw[:, :, 8 * c :]).view(np.uint64).reshape(s, rows, c)
    return lbas, ts


@spanned("codec", "meta")
def parity_oob_batch(
    codec: "StripeCodec", data_lbas: np.ndarray, data_ts: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Batched ``parity_oob``: (S, k, c) metadata -> (S, m, c) parity metadata
    in one fused encode (bit-identical to the per-stripe path)."""
    c = data_lbas.shape[2]
    rows = _meta_rows_batch(data_lbas, data_ts)
    enc = codec.encode_batch_np(rows)
    return _meta_unrows_batch(enc, c)


@spanned("codec", "meta")
def decode_meta_batch(
    codec: "StripeCodec",
    surviving_lbas: np.ndarray,
    surviving_ts: np.ndarray,
    surviving_roles: tuple[int, ...],
) -> tuple[np.ndarray, np.ndarray]:
    """Batched ``decode_meta``: (S, k, c) surviving metadata rows sharing one
    role set -> all S stripes' (k, c) data metadata in one fused decode."""
    c = surviving_lbas.shape[2]
    rows = _meta_rows_batch(surviving_lbas, surviving_ts)
    dec = codec.decode_batch_np(rows, surviving_roles)
    return _meta_unrows_batch(dec.reshape(rows.shape[0], codec.scheme.k, -1), c)


@spanned("codec", "meta")
def decode_meta(
    codec: "StripeCodec",
    surviving_lbas: np.ndarray,
    surviving_ts: np.ndarray,
    surviving_roles: tuple[int, ...],
) -> tuple[np.ndarray, np.ndarray]:
    """Reconstruct all k data rows' (lba, ts) metadata from k survivors."""
    c = surviving_lbas.shape[1]
    rows = _meta_rows(surviving_lbas, surviving_ts)
    dec = codec.decode_np(rows, surviving_roles)
    return _meta_unrows(dec.reshape(codec.scheme.k, -1), c)


def gf_coeff_matrix(k: int, m: int) -> np.ndarray:
    return gf.rs_parity_matrix(k, m)
