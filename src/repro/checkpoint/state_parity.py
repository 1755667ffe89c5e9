"""On-device erasure coding of sharded training state across DP ranks.

Beyond-paper application of ZapRAID's stripe encoding to live training
state: the k optimizer-state shards held by k data-parallel failure domains
are treated as the data chunks of a stripe, and m parity shards are computed
on-device with the same Pallas kernels (XOR for m=1, GF(256) RS for m=2).
If a DP rank dies, its optimizer shard is reconstructed from the surviving
k-1 shards + parity *without* any re-upload from checkpoint storage -- the
in-memory analogue of the paper's full-drive recovery.

All functions operate on byte-views of pytree leaves, so any dtype works.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _leaf_to_lanes(leaf: np.ndarray) -> jnp.ndarray:
    raw = np.asarray(leaf).tobytes()
    pad = (-len(raw)) % 4
    raw += b"\x00" * pad
    return ops.pack_bytes(jnp.asarray(np.frombuffer(raw, np.uint8)))


def _lanes_to_leaf(lanes: jnp.ndarray, dtype, shape, nbytes: int) -> np.ndarray:
    raw = np.asarray(ops.unpack_bytes(lanes)).tobytes()[:nbytes]
    return np.frombuffer(raw, dtype=dtype).reshape(shape).copy()


def encode_shards(shards: list, m: int = 1, *,
                  use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None) -> list:
    """Compute m parity pytrees over k rank-shard pytrees (leafwise).

    The codec mode follows the backend unless given (see
    :mod:`repro.kernels.backend`)."""
    k = len(shards)
    flat = [jax.tree.leaves(s) for s in shards]
    treedef = jax.tree.structure(shards[0])
    parity_leaves: list[list] = [[] for _ in range(m)]
    for leaves in zip(*flat):
        lanes = jnp.stack([_leaf_to_lanes(l) for l in leaves])
        if m == 1:
            p = ops.xor_parity(lanes, use_pallas=use_pallas, interpret=interpret)
            p = p[None]
        else:
            p = ops.rs_encode(lanes, m, use_pallas=use_pallas, interpret=interpret)
        ref = np.asarray(leaves[0])
        for j in range(m):
            parity_leaves[j].append(
                _lanes_to_leaf(p[j], np.uint8, (ref.nbytes + (-ref.nbytes) % 4,),
                               ref.nbytes + (-ref.nbytes) % 4)
            )
    return [jax.tree.unflatten(treedef, pl) for pl in parity_leaves]


def reconstruct_shard(
    lost_rank: int,
    surviving: dict[int, object],
    parity: list,
    k: int,
    *,
    use_pallas: Optional[bool] = None,
    interpret: Optional[bool] = None,
):
    """Rebuild rank ``lost_rank``'s shard pytree from k-1 survivors + parity."""
    m = len(parity)
    template = next(iter(surviving.values()))
    treedef = jax.tree.structure(template)
    surv_flat = {r: jax.tree.leaves(s) for r, s in surviving.items()}
    par_flat = [jax.tree.leaves(p) for p in parity]
    out_leaves = []
    t_leaves = jax.tree.leaves(template)
    for i, t in enumerate(t_leaves):
        rows, roles = [], []
        for r, leaves in surv_flat.items():
            rows.append(_leaf_to_lanes(leaves[i]))
            roles.append(r)
        for j in range(m):
            if len(rows) >= k:
                break
            rows.append(_leaf_to_lanes(par_flat[j][i]))
            roles.append(k + j)
        lanes = jnp.stack(rows[:k])
        roles = tuple(roles[:k])
        if m == 1:
            rec = ops.xor_parity(lanes, use_pallas=use_pallas, interpret=interpret)
        else:
            data = ops.rs_decode(lanes, roles, k, m,
                                 use_pallas=use_pallas, interpret=interpret)
            rec = data[lost_rank]
        ref = np.asarray(t)
        out_leaves.append(_lanes_to_leaf(rec, ref.dtype, ref.shape, ref.nbytes))
    return jax.tree.unflatten(treedef, out_leaves)
