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
