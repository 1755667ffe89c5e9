"""The wall-clock host-span recorder (``repro.obs.hostspans``): self-time
arithmetic on nested spans with an injected clock, snapshots taken with
spans still open, ``reset``, spans opened while nothing recorded, the
shared no-op of the off path, the dispatch counters, and the catalogue of
span names the program opens."""
import ast
import pathlib

import numpy as np
import pytest

from repro.integrity.checksum import R_MIN, crc32c_many
from repro.obs import HostSpans, host_span
from repro.obs import hostspans
from repro.obs.hostspans import NO_SPAN, SPANS, spanned

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"


class Clock:
    """Nanoseconds that move only when told to."""

    def __init__(self):
        self.t = 0

    def __call__(self) -> int:
        return self.t

    def tick(self, ns: int) -> None:
        self.t += ns


@pytest.fixture
def clocked():
    clock = Clock()
    rec = HostSpans(clock=clock, annotate=False).install()
    try:
        yield rec, clock
    finally:
        rec.uninstall()


def _row(snap, name):
    s = snap["spans"][name]
    return s["count"], round(s["total_s"] * 1e9), round(s["self_s"] * 1e9)


def test_nested_self_time(clocked):
    rec, clock = clocked
    with host_span("service", "loop"):
        clock.tick(10)
        with host_span("array", "stage"):
            clock.tick(5)
            with host_span("checksum", "crc32c"):
                clock.tick(7)
            clock.tick(3)
        with host_span("array", "stage"):
            clock.tick(2)
        clock.tick(1)
    snap = rec.snapshot()
    assert _row(snap, "service:loop") == (1, 28, 11)
    assert _row(snap, "array:stage") == (2, 17, 10)
    assert _row(snap, "checksum:crc32c") == (1, 7, 7)
    # self times partition the root's duration
    assert sum(s["self_s"] for s in snap["spans"].values()) == pytest.approx(28e-9)


def test_decorator_is_one_span(clocked):
    rec, clock = clocked

    @spanned("array", "build")
    def build(n):
        clock.tick(n)
        return n

    assert build(4) == 4 and build(6) == 6
    assert _row(rec.snapshot(), "array:build") == (2, 10, 10)


def test_snapshot_counts_open_spans_up_to_now(clocked):
    rec, clock = clocked
    with host_span("service", "loop"):
        clock.tick(4)
        with host_span("media", "append"):
            clock.tick(6)
        with host_span("client", "callback"):
            clock.tick(5)
            snap = rec.snapshot()   # as the benchmark's on_close does
            clock.tick(100)
    assert _row(snap, "service:loop") == (1, 15, 4)
    assert _row(snap, "client:callback") == (1, 5, 5)
    assert _row(snap, "media:append") == (1, 6, 6)
    # the snapshot is a copy: closing the spans later does not change it
    assert _row(rec.snapshot(), "service:loop") == (1, 115, 4)


def test_reset_drops_everything_and_open_spans_count_from_it(clocked):
    rec, clock = clocked
    with host_span("codec", "issue", op="decode", shapes=((3, 8),)):
        clock.tick(9)
    with host_span("service", "loop"):
        clock.tick(3)
        rec.reset()
        clock.tick(2)
    snap = rec.snapshot()
    assert set(snap["spans"]) == {"service:loop"}
    assert _row(snap, "service:loop") == (1, 2, 2)
    assert snap["dispatches"] == {}


def test_span_opened_while_off_stays_a_no_op():
    assert hostspans.current() is None
    span = host_span("array", "fetch")
    rec = HostSpans(clock=Clock(), annotate=False).install()
    try:
        with span:
            with host_span("media", "read"):
                pass
        snap = rec.snapshot()
    finally:
        rec.uninstall()
    assert set(snap["spans"]) == {"media:read"}


def test_off_path_returns_the_shared_no_op_and_records_nothing():
    assert hostspans.current() is None
    rec = HostSpans(clock=Clock(), annotate=False)   # made, not installed
    a = host_span("array", "stage")
    b = host_span("codec", "issue", op="encode", shapes=((1, 2),))
    assert a is NO_SPAN and b is NO_SPAN
    with a:
        with b:
            pass
    calls = []

    @spanned("array", "gc")
    def gc():
        calls.append(1)

    gc()
    assert calls == [1]
    assert rec.snapshot() == {"spans": {}, "dispatches": {}}


def test_dispatch_counters_key_on_op_and_shapes(clocked):
    rec, _ = clocked
    for _ in range(3):
        with host_span("codec", "issue", op="decode", shapes=((3, 1024),)):
            pass
    with host_span("codec", "issue", op="encode_batch_async",
                   shapes=((4, 3, 1024),)):
        pass

    class Codec:
        @spanned("codec", "issue", dispatch=True)
        def decode_batch(self, x, roles):
            return x

    Codec().decode_batch(np.zeros((8, 3, 1024), np.int32), (0, 1, 3))
    snap = rec.snapshot()
    assert snap["dispatches"] == {("decode", ((3, 1024),)): 3,
                                  ("encode_batch_async", ((4, 3, 1024),)): 1,
                                  ("decode_batch", ((8, 3, 1024),)): 1}
    assert snap["spans"]["codec:issue"]["count"] == 5


def test_exception_closes_the_span(clocked):
    rec, clock = clocked
    with pytest.raises(KeyError):
        with host_span("service", "loop"):
            with host_span("media", "read"):
                clock.tick(3)
                raise KeyError
    snap = rec.snapshot()
    assert _row(snap, "media:read") == (1, 3, 3)
    assert _row(snap, "service:loop") == (1, 3, 0)


def test_uninstall_only_removes_its_own_recorder():
    a = HostSpans(annotate=False).install()
    b = HostSpans(annotate=False)
    b.uninstall()
    assert hostspans.current() is a
    a.uninstall()
    assert hostspans.current() is None


@pytest.mark.parametrize("device", (False, True))
def test_crc32c_many_opens_one_span_keyed_by_its_path(device):
    """Either path is one checksum:crc32c span and no other (its self time
    is its total), keyed by the path and ``(N, L)``; the CRCs are the same
    bits with the recorder on or off."""
    blocks = np.random.default_rng(5).integers(0, 256, (R_MIN + 3, 512),
                                               dtype=np.uint8)
    off = crc32c_many(blocks, device=device)
    rec = HostSpans(annotate=False).install()
    try:
        on = crc32c_many(blocks, device=device)
    finally:
        rec.uninstall()
    snap = rec.snapshot()
    assert list(snap["spans"]) == ["checksum:crc32c"]
    s = snap["spans"]["checksum:crc32c"]
    assert s["count"] == 1 and s["self_s"] == s["total_s"] > 0
    op = "crc32c_device" if device else "crc32c_host"
    assert snap["dispatches"] == {(op, ((R_MIN + 3, 512),)): 1}
    np.testing.assert_array_equal(on, off)
    np.testing.assert_array_equal(on, crc32c_many(blocks, device=not device))


def _call_sites():
    """``(file, layer, op)`` of every ``host_span``/``spanned`` call in the
    program's sources."""
    for path in sorted(SRC.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in ("host_span", "spanned")
                    and path.name != "hostspans.py"):
                layer, op = (a.value for a in node.args[:2])
                yield path.relative_to(SRC), layer, op


def test_every_span_the_program_opens_is_catalogued():
    sites = list(_call_sites())
    names = {f"{layer}:{op}" for _, layer, op in sites}
    assert names == set(SPANS), names ^ set(SPANS)
    assert len(SPANS) == len(set(SPANS))
