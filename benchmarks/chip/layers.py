"""Host-clock spans at the program's layer boundaries, for the traced run.

The wrappers live here, in the benchmark, around the calls into each layer
of the program: class methods and module attributes are replaced for the
traced run only and put back afterwards.  Each wrapper

* times its call on the host clock and keeps the layer's *self* time: the
  span less the spans of the calls it made into other wrapped functions;
* opens a ``jax.profiler.TraceAnnotation`` named ``<layer>:<function>``, so
  that the device trace's idle gaps can be put down to what the host was
  doing;
* for the codec's jitted entry points, counts each dispatch by op and
  argument shapes, from which ``kernels/*.py`` compute the bytes moved.

``CompileClock`` sums JAX's backend-compile events (copied from
``chip_smoke.py``), to split compile time out of set-up and to count
compiles inside the window.
"""
from __future__ import annotations

import collections
import importlib
import time

# (layer, module, class or None for module attributes, attribute names)
POINTS = (
    ("array", "repro.core.array", "ZapRAIDArray",
     ("write", "read", "flush", "maybe_gc")),
    ("codec", "repro.core.raid", "StripeCodec",
     ("_to_device", "materialize", "encode", "decode", "encode_batch",
      "decode_batch", "encode_np", "decode_np", "encode_batch_np",
      "decode_batch_np", "encode_batch_async", "decode_batch_async")),
    ("codec", "repro.core.array", None,
     ("parity_oob", "parity_oob_batch", "decode_meta", "decode_meta_batch")),
    ("codec", "repro.kernels.ops", None,
     ("pack_bytes_np", "unpack_bytes_np")),
    ("checksum", "repro.core.array", None, ("crc32c_many",)),
    ("checksum", "repro.core.zns", None, ("crc32c_many",)),
)
# the codec's jitted entry points: each call is one dispatch to the device
CODEC_OPS = ("xor_parity", "rs_matmul", "xor_parity_batch", "rs_matmul_batch",
             "xor_parity_batch_device", "rs_matmul_batch_device")


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit records
    its retrieval time instead, so a warm cache shows as fewer seconds)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


class LayerSpans:
    """Self time per layer and dispatch counts per (op, shapes), while
    ``active``; installed with :meth:`install`, removed with
    :meth:`uninstall`."""

    def __init__(self):
        self.active = False
        self.self_s: dict[str, float] = collections.defaultdict(float)
        self.dispatches: collections.Counter = collections.Counter()
        self._stack: list[float] = []   # child seconds of each open span
        self._patches: list[tuple[object, str, object]] = []

    def _wrap(self, layer: str, label: str, fn, op: str | None = None):
        from jax.profiler import TraceAnnotation
        spans = self

        def wrapped(*args, **kw):
            if not spans.active:
                return fn(*args, **kw)
            if op is not None:
                shapes = tuple(tuple(a.shape) for a in args if hasattr(a, "shape"))
                spans.dispatches[(op, shapes)] += 1
            with TraceAnnotation(label):
                spans._stack.append(0.0)
                t0 = time.perf_counter()
                try:
                    return fn(*args, **kw)
                finally:
                    elapsed = time.perf_counter() - t0
                    child = spans._stack.pop()
                    spans.self_s[layer] += elapsed - child
                    if spans._stack:
                        spans._stack[-1] += elapsed

        wrapped.__wrapped__ = fn
        return wrapped

    def _patch(self, owner, attr: str, layer: str, label: str, op=None):
        # a class keeps its own function (not a bound method) to put back
        original = owner.__dict__[attr] if isinstance(owner, type) else getattr(owner, attr)
        self._patches.append((owner, attr, original))
        setattr(owner, attr, self._wrap(layer, label, original, op))

    def install(self, extra=()) -> "LayerSpans":
        """Wrap every point of ``POINTS`` and the codec ops; ``extra`` adds
        ``(layer, owner, attrs)`` points of the benchmark's own (the
        client), so that the service's time excludes them."""
        for layer, mod_name, cls_name, attrs in POINTS:
            mod = importlib.import_module(mod_name)
            owner = getattr(mod, cls_name) if cls_name else mod
            for attr in attrs:
                self._patch(owner, attr, layer, f"{layer}:{attr}")
        for layer, owner, attrs in extra:
            for attr in attrs:
                self._patch(owner, attr, layer, f"{layer}:{attr}")
        ops = importlib.import_module("repro.kernels.ops")
        for op in CODEC_OPS:
            self._patch(ops, op, "codec", f"codec:{op}", op=op)
        return self

    def uninstall(self) -> None:
        while self._patches:
            owner, attr, original = self._patches.pop()
            setattr(owner, attr, original)

    def snapshot(self) -> dict:
        return {
            "self_s": dict(self.self_s),
            "dispatches": dict(self.dispatches),
        }
