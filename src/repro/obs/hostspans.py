"""Wall-clock host spans on the profiler's clock (DESIGN.md §13).

:class:`~repro.obs.trace.Tracer` records the drive model's *virtual*
time.  This module records the other half: how long the host spends in
each layer of the program, on the wall clock, so that a profile of the
chip can say what the host was doing while the device sat idle.

The program brackets each layer boundary with::

    with host_span("codec", "issue", op="decode", shapes=(x.shape,)):
        ...

or a whole method with ``@spanned("array", "build")``.

With no recorder installed (the default) ``host_span`` returns one
shared no-op object: the cost is a call and one global read, nothing is
allocated for the span and nothing is recorded.  With a
:class:`HostSpans` installed (:meth:`HostSpans.install`, one per process)
each span

* opens a ``jax.profiler.TraceAnnotation`` named ``<layer>:<op>``, with
  the metadata as the event's stats, so the span lies on the same clock
  as the device's events in a profile;
* counts, per ``<layer>:<op>``, the calls, the total and the *self*
  nanoseconds on ``time.perf_counter_ns`` -- self time is the span's
  duration less the spans of the same recorder opened inside it;
* counts spans that carry an ``op`` by ``(op, shapes)``: the codec's
  dispatch counters.

Like the tracer it is observe-only: it reads the clock and changes no
result, so results are bit-identical with it on or off
(``tests/test_obs.py::test_tracing_is_observe_only``).  Spans sit at call
or group granularity, never inside a per-block loop.  :data:`SPANS`
lists every name the program opens.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Optional

# every span the program opens, by layer (DESIGN.md §13)
SPANS = (
    "service:loop", "service:arrive", "service:dispatch", "service:complete",
    "service:handle", "client:callback",
    "array:stage", "array:build", "array:commit", "array:bookkeep",
    "array:fetch", "array:reconstruct", "array:gc",
    "media:append", "media:read", "media:book",
    "checksum:crc32c",
    "codec:h2d", "codec:issue", "codec:wait", "codec:d2h", "codec:pack",
    "codec:meta",
    "ckpt:d2h", "ckpt:pack", "ckpt:manifest",
)


class _NoSpan:
    """The span handed out while nothing records: enters and exits."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()
_recorder: Optional["HostSpans"] = None


def host_span(layer: str, op: str, /, **meta):
    """A context manager timing ``<layer>:<op>``; the shared no-op while no
    recorder is installed.  ``meta`` may carry an ``op`` of its own (the
    dispatch counters' key)."""
    rec = _recorder
    if rec is None:
        return NO_SPAN
    return _Span(rec, f"{layer}:{op}", meta)


def spanned(layer: str, op: str, /, dispatch: bool = False):
    """Decorator: the whole call is one ``<layer>:<op>`` span.  While no
    recorder is installed the function runs after one global read.  With
    ``dispatch`` the span carries the function's name as ``op`` and the
    shapes of its array arguments as ``shapes``: a dispatch counter."""
    name = f"{layer}:{op}"

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kw):
            rec = _recorder
            if rec is None:
                return fn(*args, **kw)
            meta = {}
            if dispatch:
                meta = {"op": fn.__name__, "shapes": tuple(
                    tuple(a.shape) for a in args if hasattr(a, "shape"))}
            with _Span(rec, name, meta):
                return fn(*args, **kw)
        return wrapped
    return deco


def current() -> Optional["HostSpans"]:
    """The installed recorder, or None."""
    return _recorder


def _annotation_meta(meta: dict) -> dict:
    """Metadata as the profiler stores it: ``x``-joined shapes (a comma
    would split the stat)."""
    out = {}
    for k, v in meta.items():
        if isinstance(v, tuple):
            v = "+".join("x".join(map(str, s)) if isinstance(s, tuple) else str(s)
                         for s in v)
        out[k] = v
    return out


class _Span:
    __slots__ = ("rec", "name", "meta", "t0", "child", "ann")

    def __init__(self, rec: "HostSpans", name: str, meta: dict):
        self.rec = rec
        self.name = name
        self.meta = meta
        self.child = 0
        self.ann = None

    def _open_annotation(self) -> None:
        self.ann = self.rec._annotation(self.name, **_annotation_meta(self.meta))
        self.ann.__enter__()

    def __enter__(self):
        rec = self.rec
        if rec._annotation is not None:
            self._open_annotation()
        rec._stack.append(self)
        self.t0 = rec._clock()
        return self

    def __exit__(self, *exc):
        rec = self.rec
        elapsed = rec._clock() - self.t0
        stack = rec._stack
        if stack and stack[-1] is self:
            stack.pop()
        else:
            stack.remove(self)
        rec._add(self.name, elapsed, elapsed - self.child)
        if "op" in self.meta:
            key = (self.meta["op"], self.meta.get("shapes"))
            rec.dispatches[key] = rec.dispatches.get(key, 0) + 1
        if stack:
            stack[-1].child += elapsed
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class HostSpans:
    """Per-``<layer>:<op>`` counts, total and self nanoseconds.

    ``clock`` returns nanoseconds (``time.perf_counter_ns``); ``annotate``
    opens a profiler annotation per span (off in unit tests that inject a
    clock)."""

    def __init__(self, clock: Callable[[], int] = time.perf_counter_ns,
                 annotate: bool = True):
        self._clock = clock
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self._stack: list[_Span] = []
        self.stats: dict[str, list[int]] = {}   # name -> [count, total, self]
        self.dispatches: dict[tuple, int] = {}   # (op, shapes) -> spans

    def install(self) -> "HostSpans":
        """Make this the process's recorder; returns it."""
        global _recorder
        _recorder = self
        return self

    def uninstall(self) -> None:
        """Stop recording, if this is the recorder.  Spans still open close
        on it."""
        global _recorder
        if _recorder is self:
            _recorder = None

    def _add(self, name: str, total: int, self_ns: int) -> None:
        s = self.stats.get(name)
        if s is None:
            self.stats[name] = [1, total, self_ns]
        else:
            s[0] += 1
            s[1] += total
            s[2] += self_ns

    def reset(self) -> None:
        """Drop everything recorded; spans still open count from now."""
        self.stats = {}
        self.dispatches = {}
        now = self._clock()
        for span in self._stack:
            span.t0 = now
            span.child = 0

    def snapshot(self) -> dict:
        """What was recorded, spans still open counted up to now.

        ``{"spans": {name: {"count", "total_s", "self_s"}}, "dispatches":
        {(op, shapes): n}}``.  The open spans' profiler annotations are
        closed here and opened again, so a profile stopped right after
        holds them up to this moment."""
        now = self._clock()
        stats = {k: list(v) for k, v in self.stats.items()}
        inner = 0   # elapsed of the open span nested in the current one
        for span in reversed(self._stack):
            elapsed = now - span.t0
            s = stats.setdefault(span.name, [0, 0, 0])
            s[0] += 1
            s[1] += elapsed
            s[2] += elapsed - span.child - inner
            inner = elapsed
        if self._annotation is not None:
            for span in reversed(self._stack):
                span.ann.__exit__(None, None, None)
            for span in self._stack:
                span._open_annotation()
        return {
            "spans": {k: {"count": c, "total_s": t * 1e-9, "self_s": s * 1e-9}
                      for k, (c, t, s) in stats.items()},
            "dispatches": dict(self.dispatches),
        }
