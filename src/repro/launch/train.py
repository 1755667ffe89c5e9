"""End-to-end training driver (example + integration target).

Runs a real training loop on the local devices (CPU smoke sizes by default,
the published widths with ``--no-smoke``), with:

* deterministic synthetic data pipeline,
* AdamW (+ optional gradient compression),
* ZapRAID-backed checkpointing every ``--ckpt-every`` steps, into a RAID-5
  engine whose geometry is sized from the train state's bytes,
* failure injection (``--fail-lane N --fail-at S``) exercising degraded
  restore mid-run,
* crash-restart determinism check (``--restart-at``): the loop restores and
  the loss trace must continue identically.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --steps 20
  PYTHONPATH=src python -m repro.launch.train --no-smoke --steps 6   # full width
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.checkpoint.zapraid_ckpt import CheckpointConfig, CheckpointEngine
from repro.configs import get_config
from repro.data.pipeline import DataConfig, batch_for_step
from repro.models.config import ModelConfig, smoke
from repro.optim import adamw
from repro.train import steps as steps_mod


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True,
                    help="shrink the model to CPU-test size; --no-smoke runs "
                         "the published widths")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--compression", default="none", choices=["none", "int8", "topk"])
    ap.add_argument("--fail-lane", type=int, default=-1)
    ap.add_argument("--fail-at", type=int, default=-1)
    ap.add_argument("--restart-at", type=int, default=-1)
    return ap.parse_args(argv)


def model_config(args: argparse.Namespace) -> ModelConfig:
    cfg = get_config(args.arch)
    return smoke(cfg) if args.smoke else cfg


@dataclasses.dataclass
class TrainRun:
    """What a run leaves behind: the live state, the engine holding its
    checkpoints, and the jitted step to continue from either."""

    cfg: ModelConfig
    data: DataConfig
    train_step: Callable
    engine: CheckpointEngine
    params: Any
    opt_state: Any
    losses: list[float]

    def state(self) -> dict:
        return {"params": self.params, "opt": self.opt_state}

    def step(self, state: dict, step: int) -> tuple[dict, float]:
        """One step from ``state`` on the batch of ``step``: (state, loss)."""
        params, opt_state, metrics = self.train_step(
            state["params"], state["opt"], batch_for_step(self.data, self.cfg, step)
        )
        return {"params": params, "opt": opt_state}, float(metrics["loss"])


def run(argv=None) -> TrainRun:
    args = parse_args(argv)
    cfg = model_config(args)
    opt_cfg = adamw.AdamWConfig(compression=args.compression, warmup_steps=10)
    model, train_step = steps_mod.make_train_step(cfg, opt_cfg)

    params = model.init(jax.random.PRNGKey(0))
    opt_state = steps_mod.init_opt_state(model, params, opt_cfg)
    tr = TrainRun(
        cfg=cfg,
        data=DataConfig(args.global_batch, args.seq_len, cfg.vocab),
        train_step=jax.jit(train_step),
        engine=CheckpointEngine.for_state(
            {"params": params, "opt": opt_state},
            CheckpointConfig(n_lanes=4, scheme="raid5"),
        ),
        params=params,
        opt_state=opt_state,
        losses=[],
    )
    engine = tr.engine

    state = tr.state()
    step = 0
    t0 = time.time()
    while step < args.steps:
        state, loss = tr.step(state, step)
        tr.losses.append(loss)
        step += 1
        if step % args.ckpt_every == 0:
            engine.save(step, state)
            print(f"step {step}: loss={loss:.4f} (checkpointed)")
        else:
            print(f"step {step}: loss={loss:.4f}")

        if step == args.fail_at and args.fail_lane >= 0:
            print(f"!! injecting storage-lane failure: lane {args.fail_lane}")
            engine.fail_lane(args.fail_lane)

        if step == args.restart_at:
            print("!! simulating preemption: restore from latest checkpoint")
            args.restart_at = -1  # one-shot
            last = max(engine.catalog)
            state = jax.tree.map(jnp.asarray, engine.restore(last, state))
            step = last

    tr.params, tr.opt_state = state["params"], state["opt"]
    dt = time.time() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s; "
          f"final loss {tr.losses[-1]:.4f}; ckpt stats: {engine.stats()}")
    return tr


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    run()
