"""The program's own host spans (``repro.obs.hostspans``) in the traced run.

``layers.py`` times the program from outside, by wrapping its functions.
The program also brackets its layers itself: wall-clock spans named
``<layer>:<op>`` (``service:loop``, ``array:stage``, ``codec:wait``, ...)
with a count, total and self time each, recorded while a
``repro.obs.HostSpans`` is installed, and each a profiler annotation that
``tracereduce.attribute_gaps`` puts idle device time down to.

The metric readers that read these spans import this module, and only a
traced run loads per-layer readers.  On import it hooks a recorder onto
``layers.LayerSpans``, the traced window's own bracket:

* ``install``, right before the window opens, installs a fresh recorder;
* ``snapshot``, which the window's ``on_close`` calls before the profile
  stops, adds the recorder's snapshot under :data:`KEY`, spans still open
  (the event loop's root among them) counted up to that moment;
* ``uninstall`` removes the recorder.

A program without the recorder gets no hook: :func:`snapshot` then finds
nothing, and every reader returns ``None``.
"""
from __future__ import annotations

from typing import Optional

import layers

try:
    from repro.obs.hostspans import HostSpans
except ImportError:   # a program that predates the recorder
    HostSpans = None

KEY = "program"
MiB = 1 << 20


def _hook() -> None:
    cls = layers.LayerSpans
    if HostSpans is None or getattr(cls, "_program_spans", False):
        return
    install, snapshot, uninstall = cls.install, cls.snapshot, cls.uninstall

    def install_with_recorder(self, *args, **kw):
        out = install(self, *args, **kw)
        self.program = HostSpans().install()
        return out

    def snapshot_with_recorder(self):
        snap = snapshot(self)
        rec = getattr(self, "program", None)
        if rec is not None:
            snap[KEY] = rec.snapshot()
        return snap

    def uninstall_with_recorder(self):
        uninstall(self)
        rec = getattr(self, "program", None)
        if rec is not None:
            rec.uninstall()
            self.program = None

    cls.install = install_with_recorder
    cls.snapshot = snapshot_with_recorder
    cls.uninstall = uninstall_with_recorder
    cls._program_spans = True


_hook()


def snapshot(w) -> Optional[dict]:
    """The window's program spans: ``{"spans": {name: {"count", "total_s",
    "self_s"}}, "dispatches": {(op, shapes): n}}``, or None."""
    return (w.spans or {}).get(KEY)


def self_s(w, *names: str, layer: Optional[str] = None) -> Optional[float]:
    """Summed self seconds of the named spans, or of every span of
    ``layer``; 0.0 for spans that never opened."""
    snap = snapshot(w)
    if snap is None:
        return None
    return sum(s["self_s"] for name, s in snap["spans"].items()
               if name in names or name.split(":")[0] == layer)


def count(w, name: str) -> Optional[int]:
    snap = snapshot(w)
    if snap is None:
        return None
    return snap["spans"].get(name, {}).get("count", 0)


def per_mib_ms(w, op: str, *names: str,
               layer: Optional[str] = None) -> Optional[float]:
    """Self milliseconds of the spans per user MiB of ``op``."""
    return w.per_mib_ms(self_s(w, *names, layer=layer), op)
