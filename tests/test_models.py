"""Per-architecture smoke tests (reduced configs, same family): one forward
and one train step on CPU asserting output shapes and no NaNs, plus
decode-vs-forward consistency for the cache/state machinery."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models.config import smoke
from repro.models.model import build_model
from repro.optim import adamw
from repro.train import steps as steps_mod


def make_batch(cfg, b=2, t=16):
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, t)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, t)), jnp.int32),
    }
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            0.1 * rng.standard_normal((b, cfg.enc_len, cfg.d_model)), jnp.float32
        )
    if cfg.family == "vlm":
        batch["vis_embeds"] = jnp.asarray(
            0.1 * rng.standard_normal((b, cfg.vis_prefix_len, cfg.vis_embed_dim)),
            jnp.float32,
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = smoke(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    loss = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=2)
    _, train_step = steps_mod.make_train_step(cfg, opt_cfg)
    opt_state = steps_mod.init_opt_state(model, params, opt_cfg)
    p2, o2, metrics = jax.jit(train_step)(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params must actually change
    delta = sum(
        float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2))
    )
    assert delta > 0, f"{arch}: optimizer produced no update"


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_param_count_matches_config_formula(arch):
    cfg = smoke(get_config(arch))
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    predicted = cfg.param_count()
    assert abs(actual - predicted) / actual < 0.05, (
        f"{arch}: param_count() {predicted} vs actual {actual}"
    )


def test_qwen2_5_3b_has_its_published_parameter_count():
    """Tied embeddings, as ``Qwen/Qwen2.5-3B``'s ``tie_word_embeddings``:
    the model card's 3.09 B parameters, QKV biases included.  Shapes only:
    nothing of the full width is allocated."""
    model = build_model(get_config("qwen2.5-3b"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)) == 3_085_938_688
    assert "lm_head" not in shapes


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2.5-3b", "mamba2-1.3b",
                                  "zamba2-2.7b", "whisper-small",
                                  "llama4-scout-17b-a16e"])
def test_decode_matches_forward(arch):
    """prefill(t tokens) + decode_step x k must equal forward(t+k tokens).

    MoE archs need ample routing capacity here: capacity-dropping changes
    teacher-forced activations vs decode (where the single token always
    fits), which is expected behaviour, not a cache bug."""
    cfg = smoke(get_config(arch), capacity_factor=16.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    b, t, extra = 2, 12, 3
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, t + extra)), jnp.int32)
    batch = {"tokens": tokens[:, :t]}
    full = {"tokens": tokens}
    if cfg.family == "encdec":
        frames = jnp.asarray(
            0.1 * rng.standard_normal((b, cfg.enc_len, cfg.d_model)), jnp.float32
        )
        batch["frames"] = frames
        full["frames"] = frames
    if cfg.family == "vlm":
        vis = jnp.asarray(
            0.1 * rng.standard_normal((b, cfg.vis_prefix_len, cfg.vis_embed_dim)),
            jnp.float32,
        )
        batch["vis_embeds"] = vis
        full["vis_embeds"] = vis

    logits_pref, cache = jax.jit(model.prefill)(params, batch)

    # full-forward reference logits at the decoded positions
    full["labels"] = full["tokens"]
    x_logits = _forward_logits(model, cfg, params, full)

    # grow attention caches to t+extra capacity
    def grow(c):
        out = dict(c)
        for kname in ("k", "v", "ak", "av"):
            if kname in out:
                arr = out[kname]
                pad = [(0, 0)] * arr.ndim
                pad[2] = (0, extra)
                out[kname] = jnp.pad(arr, pad)
        return out

    cache = grow(cache)
    step = jax.jit(model.decode_step)
    logits = logits_pref
    for i in range(extra):
        np.testing.assert_allclose(
            np.asarray(logits[:, -1], np.float32),
            np.asarray(x_logits[:, t - 1 + i], np.float32),
            atol=2e-2, rtol=2e-2,
        )
        logits, cache = step(params, cache, tokens[:, t + i : t + i + 1])


def _forward_logits(model, cfg, params, batch):
    """Teacher-forced logits over the full sequence (loss path, pre-CE)."""
    import repro.models.model as mm
    import repro.models.layers as L

    if cfg.family in ("dense", "moe", "vlm"):
        x = model._inputs(params, batch)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        x, _ = model._trunk(params, x, positions)
        if cfg.family == "vlm" and "vis_embeds" in batch:
            x = x[:, batch["vis_embeds"].shape[1]:, :]
        return model._logits(params, x)
    if cfg.family in ("ssm", "hybrid"):
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        x, _, _ = model._trunk(params, x, positions)
        return jnp.einsum("btd,dv->btv", x, params["lm_head"])
    # encdec
    enc_out = model._encode(params, batch["frames"])
    ck, cv = model._cross_kv(params, enc_out)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def fwd(h, xs):
        p_layer, k, v = xs
        h2, _ = model._dec_layer(p_layer, h, positions, k, v)
        return h2, 0

    x, _ = jax.lax.scan(fwd, x, (params["dec_layers"], ck, cv))
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("btd,dv->btv", x, params["embed"].T) * cfg.d_model ** -0.5


def test_blocked_attention_equals_dense():
    """Blocked causal attention must be exact vs the naive formulation."""
    from repro.models.layers import blocked_causal_attention

    rng = np.random.default_rng(3)
    b, t, h, kv, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, kv, hd)), jnp.float32)
    out_blocked = blocked_causal_attention(q, k, v, q_block=16)
    out_full = blocked_causal_attention(q, k, v, q_block=t)
    np.testing.assert_allclose(
        np.asarray(out_blocked), np.asarray(out_full), atol=1e-5, rtol=1e-5
    )


def test_moe_routing_conservation():
    """Every kept token's outputs are scaled by normalized top-k probs; with
    capacity ample, outputs must be finite and nonzero for all tokens."""
    from repro.models.layers import init_moe, moe_apply

    cfg = smoke(get_config("grok-1-314b"), n_experts=4, capacity_factor=4.0)
    p, _ = init_moe(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 32, cfg.d_model)), jnp.float32)
    y = moe_apply(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(jnp.mean(jnp.abs(y))) > 0
