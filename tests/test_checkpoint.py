"""ZapRAID checkpoint engine + on-device state parity: save/restore
roundtrips, degraded restore after lane loss, crash remount, restart
determinism, and erasure-coded optimizer-shard reconstruction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.state_parity import encode_shards, reconstruct_shard
from repro.checkpoint.zapraid_ckpt import CheckpointConfig, CheckpointEngine


def small_engine():
    return CheckpointEngine(
        CheckpointConfig(n_lanes=4, scheme="raid5", group_size=8,
                         block_bytes=512, zone_cap_blocks=256, n_zones=64,
                         chunk_blocks=2),
        logical_blocks=1 << 13,
    )


def mk_state(seed=0, n=3):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            f"w{i}": jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
            for i in range(n)
        },
        "step": jnp.int32(seed),
        "m": {"w0": jnp.asarray(rng.standard_normal(64), jnp.bfloat16)},
    }


def trees_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(
        np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
        for x, y in zip(fa, fb)
    )


def test_save_restore_roundtrip():
    eng = small_engine()
    state = mk_state(1)
    eng.save(10, state)
    out = eng.restore(10, state)
    assert trees_equal(state, out)


def test_multiple_checkpoints_and_retirement():
    eng = small_engine()
    states = {s: mk_state(s) for s in (1, 2, 3, 4)}
    for s, st in states.items():
        eng.save(s, st)
    assert sorted(eng.catalog) == [3, 4]  # keep_last=2
    assert trees_equal(states[4], eng.restore(4, states[4]))


def test_degraded_restore_after_lane_loss():
    eng = small_engine()
    state = mk_state(7)
    eng.save(5, state)
    eng.fail_lane(2)
    out = eng.restore(5, state)  # no rebuild -- degraded reads decode
    assert trees_equal(state, out)
    assert eng.array.stats.degraded_reads > 0


def test_save_after_lane_loss_uses_hot_spare():
    eng = small_engine()
    eng.save(1, mk_state(1))
    eng.fail_lane(0)
    st2 = mk_state(2)
    eng.save(2, st2)  # must rebuild lane 0 first
    assert not eng.array.drives[0].failed
    assert trees_equal(st2, eng.restore(2, st2))


def test_crash_remount_recovers_catalog():
    eng = small_engine()
    st = mk_state(3)
    eng.save(42, st)
    eng2 = eng.crash_and_remount()
    assert 42 in eng2.catalog
    assert trees_equal(st, eng2.restore(42, st))


def test_log_structured_gc_under_many_saves():
    eng = small_engine()
    st = mk_state(0)
    for s in range(1, 14):
        eng.save(s, mk_state(s))
    last = max(eng.catalog)
    assert trees_equal(mk_state(last), eng.restore(last, st))
    assert eng.array.stats.device_blocks_written > 0


def test_engine_sized_from_state_bytes():
    """``for_state`` sizes the ring for keep_last+1 saves of the state and
    the zones for that plus GC headroom: many saves wrap the ring and GC
    the stale extents, and a degraded restore of the last one is exact."""
    from repro.checkpoint.zapraid_ckpt import MANIFEST_LBAS, state_blocks

    def big_state(seed):
        rng = np.random.default_rng(seed)
        return {"w": jnp.asarray(rng.standard_normal((4, 64, 64)), jnp.float32),
                "b": jnp.asarray(rng.standard_normal(100), jnp.bfloat16),
                "step": jnp.int32(seed)}

    cfg = CheckpointConfig(n_lanes=4, scheme="raid5", group_size=8,
                           block_bytes=512, zone_cap_blocks=256, chunk_blocks=2)
    st = big_state(0)
    eng = CheckpointEngine.for_state(st, cfg)
    per_save = state_blocks(st, cfg.block_bytes)
    assert per_save == 128 + 1 + 1
    assert eng.logical_blocks == MANIFEST_LBAS + 3 * per_save
    for s in range(1, 25):
        eng.save(s, big_state(s))
    assert eng.array.stats.gc_runs > 0
    eng.fail_lane(1)
    assert trees_equal(big_state(24), eng.restore(24, st))


# ------------------------------------------------------ state parity (EC)

@pytest.mark.parametrize("m", [1, 2])
def test_optimizer_shard_reconstruction(m):
    k = 4
    rng = np.random.default_rng(0)
    shards = [
        {
            "m": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
            "v": jnp.asarray(rng.standard_normal(33), jnp.float32),
        }
        for _ in range(k)
    ]
    parity = encode_shards(shards, m=m, use_pallas=True)
    lost = 2
    surviving = {r: shards[r] for r in range(k) if r != lost}
    rec = reconstruct_shard(lost, surviving, parity, k, use_pallas=True)
    assert trees_equal(rec, shards[lost])


def test_restart_determinism():
    """Restore + recompute must reproduce the original loss trajectory."""
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, batch_for_step
    from repro.models.config import smoke
    from repro.optim import adamw
    from repro.train import steps as steps_mod

    cfg = smoke(get_config("smollm-135m"))
    opt_cfg = adamw.AdamWConfig(warmup_steps=2)
    model, train_step = steps_mod.make_train_step(cfg, opt_cfg)
    train_step = jax.jit(train_step)
    params = model.init(jax.random.PRNGKey(0))
    opt = steps_mod.init_opt_state(model, params, opt_cfg)
    dc = DataConfig(4, 16, cfg.vocab)
    eng = small_engine()

    losses = []
    for step in range(6):
        batch = batch_for_step(dc, cfg, step)
        params, opt, m = train_step(params, opt, batch)
        losses.append(float(m["loss"]))
        if step == 2:
            eng.save(step, {"params": params, "opt": opt})

    restored = eng.restore(2, {"params": params, "opt": opt})
    p2 = jax.tree.map(jnp.asarray, restored["params"])
    o2 = jax.tree.map(jnp.asarray, restored["opt"])
    relosses = []
    for step in range(3, 6):
        batch = batch_for_step(dc, cfg, step)
        p2, o2, m = train_step(p2, o2, batch)
        relosses.append(float(m["loss"]))
    np.testing.assert_allclose(relosses, losses[3:], rtol=1e-5, atol=1e-6)


# ------------------------------------- async saves at the paper's geometry

def _timed_engine(n_zones=8, state_blocks_=None, keep_last=2):
    """An engine over a timed RAID-5 (3+1) pipeline at the paper's geometry
    (G=256 stripes, one-block chunks) on small zones, behind a block
    service; its ring holds ``keep_last + 1`` saves of ``state_blocks_``."""
    from repro.checkpoint.zapraid_ckpt import MANIFEST_LBAS
    from repro.service import BlockDeviceService, QosClass

    cfg = CheckpointConfig(n_lanes=4, scheme="raid5", group_size=256,
                           chunk_blocks=1, block_bytes=4096,
                           zone_cap_blocks=512, n_zones=n_zones,
                           keep_last=keep_last)
    logical = MANIFEST_LBAS + (keep_last + 1) * state_blocks_
    ckpt, pipe = CheckpointEngine.build_timed(cfg, logical, seed=5)
    svc = BlockDeviceService(pipe, max_inflight=64, policy="fifo")
    svc.register("ckpt", QosClass("ckpt", queue_cap=1 << 30))
    return ckpt, svc


def _bits_state(seed):
    """A train-state-like tree of drawn bits: NaN patterns included, so it
    is compared as bytes."""
    rng = np.random.default_rng(seed)

    def bits(shape, dtype):
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        return np.frombuffer(rng.bytes(n), dtype).reshape(shape)

    return {
        "params": {"w": bits((40, 2048), jnp.bfloat16), "b": bits((96,), jnp.bfloat16)},
        "opt": {"step": np.int32(seed),
                "m": {"w": bits((40, 2048), np.float32), "b": bits((96,), np.float32)},
                "v": {"w": bits((40, 2048), np.float32), "b": bits((96,), np.float32)}},
    }


def _as_bytes(state) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    return {jax.tree_util.keystr(p): np.asarray(x).tobytes() for p, x in flat}


def _save(ckpt, svc, step, state, **kw):
    ticket = ckpt.save_async(step, state, service=svc, **kw)
    svc.drain()
    assert ticket.done
    return ticket


def _restored_bytes(ckpt, svc, step, like):
    ticket = ckpt.restore_async(step, like, service=svc)
    svc.drain()
    assert ticket.done
    return _as_bytes(ticket.state)


def test_async_save_and_restore_at_the_papers_geometry():
    from repro.checkpoint.zapraid_ckpt import state_blocks

    per_save = state_blocks(_bits_state(0), 4096)
    ckpt, svc = _timed_engine(state_blocks_=per_save)
    saved = {}
    for step in (1, 2):
        saved[step] = _as_bytes(_bits_state(step))
        _save(ckpt, svc, step, _bits_state(step))
    like = _bits_state(0)
    assert _restored_bytes(ckpt, svc, 2, like) == saved[2]
    ckpt.fail_lane(1)
    d0 = ckpt.array.stats.degraded_reads
    for step in (1, 2):
        assert _restored_bytes(ckpt, svc, step, like) == saved[step]
    assert ckpt.array.stats.degraded_reads > d0


def test_async_saves_past_the_ring_and_a_segment_restore_every_kept_one():
    from repro.checkpoint.zapraid_ckpt import state_blocks
    from repro.core.segment import solve_stripes_per_segment

    per_save = state_blocks(_bits_state(0), 4096)
    stripes, _ = solve_stripes_per_segment(512, 1, 4096)
    ckpt, svc = _timed_engine(n_zones=4, state_blocks_=per_save)
    saved = {}
    step = 0
    # until GC has run, then three saves more: a segment holds several
    # saves, the ring three
    while ckpt.array.stats.gc_runs == 0 or step < gc_step + 3:
        step += 1
        saved[step] = _as_bytes(_bits_state(step))
        _save(ckpt, svc, step, _bits_state(step))
        if ckpt.array.stats.gc_runs == 0:
            gc_step = step + 1
        assert step < 100
    assert step * per_save > 3 * stripes > 3 * per_save
    assert sorted(ckpt.catalog) == [step - 1, step]
    for step in ckpt.catalog:
        assert _restored_bytes(ckpt, svc, step, _bits_state(0)) == saved[step]


def test_shard_metadata_round_trips():
    from repro.checkpoint.zapraid_ckpt import Shard, state_blocks

    state = _bits_state(3)
    # rank 1 of two along each leaf's last axis; the step whole
    shards = jax.tree.map(
        lambda x: Shard(tuple(np.shape(x)[:-1]) + (2 * np.shape(x)[-1],)
                        if np.ndim(x) else (), (0,) * (np.ndim(x) - 1)
                        + ((np.shape(x)[-1],) if np.ndim(x) else ())),
        state)
    ckpt, svc = _timed_engine(state_blocks_=state_blocks(state, 4096))
    ticket = _save(ckpt, svc, 7, state, shards=shards)
    w = ticket.manifest["leaves"]["['params']['w']"]
    assert (w["shape"], w["global_shape"], w["start"]) == ([40, 2048], [40, 4096], [0, 2048])
    step = ticket.manifest["leaves"]["['opt']['step']"]
    assert (step["shape"], step["global_shape"], step["start"]) == ([], [], [])
    # the manifest persisted with the shard fields: a remount reads them back
    remounted = ckpt.crash_and_remount()
    assert remounted.catalog[7] == ticket.manifest
    # without shards the manifest is as before: no shard fields
    plain = _save(ckpt, svc, 8, state).manifest
    assert all(set(e) == {"lba", "n_blocks", "nbytes", "dtype", "shape"}
               for e in plain["leaves"].values())
    # a slice that does not lie in its leaf is refused before anything is
    # allocated
    bad = dict(shards, params=dict(shards["params"], w=Shard((40, 2048), (0, 1))))
    with pytest.raises(ValueError, match="does not lie"):
        ckpt.save_async(9, state, service=svc, shards=bad)


def test_checkpoint_spans_open_and_leave_results_unchanged():
    from repro.checkpoint.zapraid_ckpt import state_blocks
    from repro.obs.hostspans import HostSpans

    runs = []
    for record in (False, True):
        state = _bits_state(4)
        ckpt, svc = _timed_engine(state_blocks_=state_blocks(state, 4096))
        rec = HostSpans(annotate=False).install() if record else None
        try:
            ticket = _save(ckpt, svc, 1, state)
        finally:
            if rec is not None:
                rec.uninstall()
        if rec is not None:
            spans = rec.snapshot()["spans"]
            for name in ("ckpt:d2h", "ckpt:pack", "ckpt:manifest"):
                assert spans[name]["count"] >= 1, name
        arr = ckpt.array
        runs.append((ticket.manifest, ticket.t_done, arr.l2p.flat.copy(),
                     [d.data.copy() for d in arr.drives]))
    off, on = runs
    assert off[:2] == on[:2]
    assert np.array_equal(off[2], on[2])
    assert all(np.array_equal(a, b) for a, b in zip(off[3], on[3]))
