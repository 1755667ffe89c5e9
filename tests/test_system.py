"""End-to-end behaviour tests for the full system: a training run with
checkpoint/restart + lane failure, and dry-run spec resolution for every
architecture (reduced-size lower on the local device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models.config import smoke
from repro.launch import train as train_mod


def test_end_to_end_training_with_failure_and_restart(capsys):
    losses = train_mod.run([
        "--arch", "qwen2.5-3b", "--steps", "6", "--ckpt-every", "2",
        "--fail-lane", "1", "--fail-at", "3", "--restart-at", "4",
        "--global-batch", "4", "--seq-len", "32",
    ]).losses
    assert len(losses) >= 6
    assert all(np.isfinite(l) for l in losses)


def test_no_smoke_parses_to_full_width():
    """``--no-smoke`` reaches the published config; the default stays smoke
    size.  Parsing only: no model is built."""
    full = train_mod.model_config(train_mod.parse_args(["--no-smoke"]))
    assert full == get_config("smollm-135m")
    assert (full.n_layers, full.d_model, full.vocab, full.dtype) == (
        30, 576, 49152, "bfloat16"
    )
    small = train_mod.model_config(train_mod.parse_args([]))
    assert small == smoke(get_config("smollm-135m"))


def test_compile_cache_dir_placed_from_outside(monkeypatch):
    """The entry points' cache goes to $JAX_COMPILATION_CACHE_DIR where it is
    set (and nothing else is chosen), else to the fixed <repo>/.jax_cache."""
    import pathlib

    from repro.launch import compile_cache

    repo = pathlib.Path(__file__).resolve().parents[1]
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.use_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_lower_on_local_mesh(arch):
    """Every architecture's train step lowers+compiles on the local mesh with
    the same sharding machinery the production dry-run uses."""
    from repro.distributed import sharding as sh
    from repro.optim import adamw
    from repro.train import steps as steps_mod

    cfg = smoke(get_config(arch))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    opt_cfg = adamw.AdamWConfig()
    model, train_step = steps_mod.make_train_step(cfg, opt_cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sh.param_specs(params, model.axes(), mesh, fsdp=cfg.fsdp)
    opt = jax.eval_shape(
        lambda p: steps_mod.init_opt_state(model, p, opt_cfg), params
    )
    ospecs = adamw.state_specs(pspecs, params, mesh)
    b, t = 2, 16
    batch = {
        "tokens": jax.ShapeDtypeStruct((b, t), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, t), jnp.int32),
    }
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct((b, cfg.enc_len, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["vis_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.vis_prefix_len, cfg.vis_embed_dim), jnp.float32
        )
    bspecs = {k: sh.data_spec(mesh, len(v.shape), batch_size=b) for k, v in batch.items()}
    fn = jax.jit(
        train_step,
        in_shardings=(sh.named(mesh, pspecs), sh.named(mesh, ospecs),
                      sh.named(mesh, bspecs)),
    )
    compiled = fn.lower(params, opt, batch).compile()
    assert compiled is not None
