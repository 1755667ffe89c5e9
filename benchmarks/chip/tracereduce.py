"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  :func:`events_from_profile` flattens it to plain tuples so that
everything after it is arithmetic on lists, checked by
``tests/test_trace_reduce.py`` on hand-built traces:

* device events: ``(line, name, start_ns, duration_ns)`` on the planes of
  the accelerator (``/device:TPU:<i>``);
* host spans: ``(name, start_ns, duration_ns)``, the ``layer:function``
  annotations that ``layers.py`` opens on the host.

Busy time is the union of the intervals of the device's operations, idle
is the rest of the traced window, and a program's time is the sum of the
durations of its module events (``jit_<function>``).
"""
from __future__ import annotations

import bisect
import collections
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN = re.compile(r"^[a-z]+:[A-Za-z_0-9]+$")   # layers.py annotation names


def find_xplane(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def events_from_profile(pd) -> tuple[dict, list]:
    """``({device plane: [(line, name, start_ns, dur_ns)]}, host spans)``."""
    device: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                for e in line.events:
                    evs.append((line.name, e.name, e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if SPAN.match(e.name):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return device, host


def op_events(events: list) -> list:
    """The device's operations: the ``XLA Ops`` line, or the modules where
    a trace has no op line."""
    ops = [e for e in events if e[0] == OPS_LINE]
    return ops if ops else [e for e in events if e[0] == MODULES_LINE]


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: list, t0: float, t1: float) -> float:
    """Nanoseconds of ``[t0, t1]`` in which some device operation ran."""
    clipped = [(max(s, t0), min(s + d, t1)) for _, _, s, d in op_events(events)]
    return sum(e - s for s, e in merge(c for c in clipped if c[1] > c[0]))


def idle_gaps(events: list, t0: float, t1: float) -> list[tuple[float, float]]:
    """The stretches of ``[t0, t1]`` with no device operation running."""
    busy = merge((s, s + d) for _, _, s, d in op_events(events))
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    return [(s, e) for s, e in gaps if e > s]


def module_ns(events: list, programs) -> float:
    """Summed device time of the module events of the named jitted
    functions (``jit_<name>``, with or without a ``(<id>)`` suffix)."""
    names = {f"jit_{p}" for p in programs}
    return float(sum(
        d for line, name, _, d in events
        if line == MODULES_LINE and name.split("(")[0] in names
    ))


def _op_label(module: str | None, op: str) -> str:
    """``jit_<function>/<hlo op>``: the program and the op's own name, not
    its operands (``%copy.1 = s32[...] copy(...)`` -> ``copy.1``)."""
    name = op.split(" = ", 1)[0].lstrip("%")
    return f"{module.split('(')[0]}/{name}" if module else name


def top_ops(events: list, n: int = 10) -> list[list]:
    """The ``n`` device operations that took most time, summed by program
    and op name, in seconds."""
    mods = sorted((s, s + d, name) for line, name, s, d in events
                  if line == MODULES_LINE)
    starts = [m[0] for m in mods]
    tot: dict[str, float] = collections.defaultdict(float)
    for _, name, s, d in op_events(events):
        i = bisect.bisect_right(starts, s) - 1
        module = mods[i][2] if i >= 0 and mods[i][1] >= s else None
        tot[_op_label(module, name)] += d
    return [[k, v * 1e-9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(gaps: list, host: list, n: int = 10) -> list[list]:
    """Idle device seconds summed by what the host was doing: each stretch
    of each gap goes to the innermost host span open over it, or to
    ``service`` where no wrapped layer was running (the event loop, the
    dispatcher and the handlers)."""
    marks = []   # (time, order, kind, index); at one instant ends go first
    for i, (g0, g1) in enumerate(gaps):
        marks += [(g0, 1, "gap+", i), (g1, 0, "gap-", i)]
    for j, (_, s, d) in enumerate(host):
        marks += [(s, 1, "span+", j), (s + d, 0, "span-", j)]
    marks.sort()
    open_spans: list[int] = []   # host spans nest: the last open is innermost
    in_gap = 0
    last = None
    tot: dict[str, float] = collections.defaultdict(float)
    for t, _, kind, i in marks:
        if in_gap and last is not None and t > last:
            name = host[open_spans[-1]][0] if open_spans else "service"
            tot[name] += (t - last) * 1e-9
        last = t
        if kind == "gap+":
            in_gap += 1
        elif kind == "gap-":
            in_gap -= 1
        elif kind == "span+":
            open_spans.append(i)
        else:
            open_spans.remove(i)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
