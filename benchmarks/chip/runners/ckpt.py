"""Training checkpoints: one chip's FSDP shard of a model's train state,
saved back to back through the checkpoint engine and the block service.

Traffic keys: ``op`` (``write``), ``arch`` (a ``repro.configs`` name),
``smoke`` (the architecture at ``repro.models.config.smoke`` widths, for
the CPU tests), ``fsdp`` (the chips the state is sharded over), ``keep_last``
(saves the engine keeps), ``qd`` (the service's requests in flight),
``check_blocks`` (blocks of a save checked on the media and for their
checksums).

The state is the trainer's (``{"params", "opt": adamw.init_state}``), as
rank 0 of an FSDP mesh of ``fsdp`` chips holds it: each leaf's shape from
``jax.eval_shape`` of the model's init and the repository's sharding rule
(``distributed.sharding.param_specs`` with ``fsdp=True`` over an abstract
``fsdp``-way data mesh), so nothing of the full width is allocated.  Every
leaf's bits are drawn on the device from the seed and the save's step (a
jitted fold-in), so no two saves carry the same bytes; the step counter
holds the step.

The warm-up is the block runner's write warm-up (every group encode shape),
then one whole save.  The window's saves run back to back through
``CheckpointEngine.save_async`` on one ``BlockDeviceService``, each given
the shard's global shapes and slices; a save that has started runs to its
end, and the window closes at the end of the last.  A save's sample runs
from just before its staging (the copy out of the device included) to the
acknowledgement of its manifest; it counts the state's blocks, not the
manifest's, and is ``ok`` when every request of the save succeeded.

Checks, against the plain reference (``ckpt_reference.py``):

* ``unpersisted_at_manifest_blocks``: as each counted save's manifest is
  submitted, the last block of every leaf and seeded others,
  ``check_blocks`` a save, must be on the media where the L2P puts them,
  with the reference's bytes;
* ``manifest_mismatched_entries``: the manifest read back through the
  array lists the reference's kept steps, and the last step's leaves with
  the reference's extents, dtypes, shapes, global shapes and slices;
* ``restored_mismatched_bytes``: the last save, every leaf, restored
  through ``restore_async`` on the same service, byte for byte;
* ``crc_mismatched_blocks``: ``harness.crc_mismatches`` over a seeded
  ``check_blocks`` of the last save's blocks (every leaf's last among
  them) and as many blocks written anywhere;
* ``degraded_restored_mismatched_bytes``: the same restore with as many
  drives failed as the configuration survives (seeded), and
  ``degraded_blocks_decoded``, that it decoded;
* ``saves`` and ``saved_blocks``: at least one of each.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import numpy as np

import harness
import specs
from ckpt_reference import MANIFEST_BLOCKS, CkptReference, parse_manifest
from loadgen import Sample

TENANT = "ckpt"


def shard_state(arch: str, fsdp: int, smoke: bool = False):
    """``(shapes, global_shapes)`` of rank 0's shard of the train state:
    trees of ``jax.ShapeDtypeStruct``, the shard's and the whole leaf's."""
    import jax
    from jax.sharding import AbstractMesh, PartitionSpec

    from repro.configs import get_config
    from repro.distributed.sharding import param_specs
    from repro.models.config import smoke as smoke_config
    from repro.models.model import build_model
    from repro.optim import adamw

    cfg = get_config(arch)
    model = build_model(smoke_config(cfg) if smoke else cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = AbstractMesh((fsdp,), ("data",))
    pspecs = param_specs(params, model.axes(), mesh, fsdp=True)
    full = {"params": params, "opt": jax.eval_shape(adamw.init_state, params)}
    spec_tree = {"params": pspecs, "opt": {
        "step": PartitionSpec(), "master": pspecs, "m": pspecs, "v": pspecs}}

    def shard(leaf, spec):
        parts = tuple(spec) + (None,) * (len(leaf.shape) - len(spec))
        shape = tuple(d if p is None else d // fsdp
                      for d, p in zip(leaf.shape, parts))
        return jax.ShapeDtypeStruct(shape, leaf.dtype)

    return jax.tree.map(shard, full, spec_tree), full


def state_drawer(shapes, seed: int) -> Callable:
    """``draw(step)``: the state of ``step`` on the device, every leaf's bits
    drawn from ``seed`` folded with the step, the step counter the step."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)

    # the key is an argument, not a constant of the program, so that one
    # compiled program serves every seed
    @jax.jit
    def draw(key, step):
        keys = jax.random.split(jax.random.fold_in(key, step), len(flat))
        out = []
        for k, (path, s) in zip(keys, flat):
            if jax.tree_util.keystr(path) == "['opt']['step']":
                out.append(step.astype(s.dtype))
                continue
            bits = jax.random.bits(k, s.shape, jnp.dtype(f"uint{8 * s.dtype.itemsize}"))
            out.append(jax.lax.bitcast_convert_type(bits, s.dtype))
        return jax.tree.unflatten(treedef, out)

    def run(step: int):
        return jax.block_until_ready(draw(key, np.uint32(step)))

    return run


class ManifestWatch:
    """The block service as the engine's saves see it (``engine`` and
    ``submit_write``), with one check added: as a save's manifest is
    submitted, a seeded sample of the save's blocks must
    already be on the media where the L2P puts them, with the reference's
    bytes.  ``unpersisted`` holds the count by step."""

    def __init__(self, svc, arr, ref: CkptReference, rng, n_check: int):
        self.svc = svc
        self.engine = svc.engine
        self.arr = arr
        self.ref = ref
        self.rng = rng
        self.n_check = n_check
        self.unpersisted: dict[int, int] = {}

    def submit_write(self, tenant, lba, data, *, at=None, cb=None):
        if lba == 0:   # the manifest: every extent of the save acknowledged
            step = self.ref.last_step()
            self.unpersisted[step] = self.unpersisted_blocks(step)
        return self.svc.submit_write(tenant, lba, data, at=at, cb=cb)

    def unpersisted_blocks(self, step: int) -> int:
        lbas = self.ref.sample_lbas(step, self.rng, self.n_check)
        mapped, drives, zones, offs = harness.locate(self.arr, lbas)
        got, _ = harness.media(self.arr, drives, zones, offs, mapped)
        ok = mapped & np.all(got == self.ref.blocks.blocks[lbas], axis=1)
        return int((~ok).sum())


class CkptSaves:
    """The window's work: whole saves until the deadline."""

    def __init__(self, engine, svc, watch: ManifestWatch, ref: CkptReference,
                 draw: Callable, shapes, global_shapes, op: str):
        import jax
        from repro.checkpoint.zapraid_ckpt import Shard

        self.engine = engine
        self.svc = svc
        self.watch = watch
        self.ref = ref
        self.draw = draw
        self.shapes = shapes
        self.op = op
        self.shards = jax.tree.map(
            lambda s, g: Shard(tuple(g.shape), (0,) * len(g.shape)),
            shapes, global_shapes)
        self.samples: list[Sample] = []
        self.steps: list[int] = []
        self.t_start = self.deadline = self.t_close = float("nan")
        self.on_close: Optional[Callable[[], None]] = None

    def record(self, step: int) -> None:
        """Hand the reference host copies of the state of ``step``, drawn
        apart from the one the save is given: a device array keeps the host
        copy taken of it, which the save's own copy would then reuse."""
        import jax

        state = jax.device_get(self.draw(step))
        flat, _ = jax.tree_util.tree_flatten_with_path(state)
        shards = jax.tree.leaves(self.shards)
        self.ref.record(step, [
            (jax.tree_util.keystr(p), a, sh.global_shape, sh.start)
            for (p, a), sh in zip(flat, shards)])

    def save(self, step: int) -> Sample:
        self.record(step)
        state = self.draw(step)
        s = Sample(self.op, -1, 0, time.perf_counter())
        acked = []
        ticket = self.engine.save_async(
            step, state, service=self.watch, tenant=TENANT,
            cb=lambda _t: acked.append(time.perf_counter()), shards=self.shards)
        self.svc.engine.run()
        s.t_done = acked[0] if acked else time.perf_counter()
        s.n_blocks = sum(e["n_blocks"] for e in ticket.manifest["leaves"].values())
        reqs = self.svc.cq.drain()
        s.ok = ticket.done and len(reqs) == ticket.n_extents + 1 \
            and all(r.ok() for r in reqs)
        self.samples.append(s)
        self.steps.append(step)
        return s

    def warm_up(self) -> None:
        """One whole save, of step 0, which is no sample."""
        self.save(0)
        self.samples.clear()
        self.steps.clear()

    def run(self, seconds: float) -> None:
        self.t_start = time.perf_counter()
        self.deadline = self.t_start + seconds
        step = 1
        while True:
            s = self.save(step)
            step += 1
            if s.t_done >= self.deadline:
                break
        self.t_close = s.t_done
        if self.on_close is not None:
            self.on_close()

    def in_window(self, op: str) -> list[Sample]:
        """Every save of the window that succeeded: the window closes at the
        end of the last."""
        return [s for s in self.samples if s.op == op and s.ok]

    def restore(self, step: int) -> dict:
        """Leaf path -> array of the save of ``step``, restored through the
        service."""
        import jax

        ticket = self.engine.restore_async(step, self.shapes, service=self.svc,
                                           tenant=TENANT)
        self.svc.engine.run()
        self.svc.cq.drain()
        if not ticket.done:
            return {}
        flat, _ = jax.tree_util.tree_flatten_with_path(ticket.state)
        return {jax.tree_util.keystr(p): a for p, a in flat}


# the client's own work inside the window, kept out of the service's time
CLIENT = (
    ("client", CkptSaves, ("record",)),
    ("client", ManifestWatch, ("unpersisted_blocks",)),
)


def prepare(b, ref, cell, rngs: dict) -> CkptSaves:
    from repro.checkpoint.zapraid_ckpt import (MANIFEST_LBAS,
                                               CheckpointConfig,
                                               CheckpointEngine, state_blocks)
    from repro.service import BlockDeviceService, QosClass

    traffic = cell.traffic
    arr = b.arr
    bb = arr.zns_cfg.block_bytes
    shapes, global_shapes = shard_state(traffic["arch"], traffic["fsdp"],
                                        traffic.get("smoke", False))
    keep = traffic["keep_last"]
    ring = MANIFEST_LBAS + (keep + 1) * state_blocks(shapes, bb)
    if b.volume_blocks < ring:
        raise harness.HarnessError(
            f"a ring of {keep + 1} saves of {state_blocks(shapes, bb)} blocks "
            f"and a {MANIFEST_LBAS}-block manifest needs {ring} blocks; the "
            f"volume has {b.volume_blocks}")
    specs.load_runner("block").warm_up(b, traffic, rngs["traffic"])
    ccfg = CheckpointConfig(
        n_lanes=arr.cfg.n_drives, scheme=arr.cfg.scheme,
        group_size=arr.cfg.group_size, chunk_blocks=arr.cfg.chunk_blocks,
        block_bytes=bb, zone_cap_blocks=arr.zns_cfg.zone_cap_blocks,
        n_zones=arr.zns_cfg.n_zones, keep_last=keep)
    engine = CheckpointEngine(ccfg, b.volume_blocks, array=arr)
    svc = BlockDeviceService(b.pipe, max_inflight=traffic["qd"], policy="fifo")
    svc.register(TENANT, QosClass(TENANT, queue_cap=1 << 30))
    cref = CkptReference(ref, keep)
    watch = ManifestWatch(svc, arr, cref, rngs["check"], traffic["check_blocks"])
    draw = state_drawer(shapes, int(rngs["payload"].integers(1 << 31)))
    work = CkptSaves(engine, svc, watch, cref, draw, shapes, global_shapes,
                     traffic["op"])
    work.warm_up()
    return work


def check(b, ref, work: CkptSaves, cell, rng, stats_window: dict) -> dict:
    """Every number compared, each with its limit and rule."""
    arr, cref = b.arr, work.ref
    counted = work.in_window(cell.traffic["op"])
    counted_steps = [st for s, st in zip(work.samples, work.steps) if s.ok]
    last = cref.last_step()
    n_check = cell.traffic["check_blocks"]
    checks = {"unpersisted_at_manifest_blocks": (
        sum(work.watch.unpersisted.get(st, n_check) for st in counted_steps),
        0, "<=")}
    manifest = parse_manifest(arr.read(0, MANIFEST_BLOCKS))
    checks["manifest_mismatched_entries"] = (
        cref.manifest_mismatches(manifest), 0, "<=")
    checks["restored_mismatched_bytes"] = (
        cref.mismatched_bytes(last, work.restore(last)), 0, "<=")
    lbas = cref.sample_lbas(last, rng, n_check)
    checks["crc_mismatched_blocks"] = (
        harness.crc_mismatches(arr, cref.blocks, lbas, rng, n_check), 0, "<=")
    losses = cell.config["guarantee"]["drive_losses_survived"]
    d0 = arr.stats.degraded_reads
    for d in sorted(rng.choice(arr.cfg.n_drives, losses, replace=False)):
        arr.fail_drive(int(d))
    checks["degraded_restored_mismatched_bytes"] = (
        cref.mismatched_bytes(last, work.restore(last)), 0, "<=")
    checks["degraded_blocks_decoded"] = (arr.stats.degraded_reads - d0, 1, ">=")
    checks["saves"] = (len(counted), 1, ">=")
    checks["saved_blocks"] = (sum(s.n_blocks for s in counted), 1, ">=")
    return checks
