"""The program's own host spans (``repro.obs.hostspans``, recorded by the
harness's traced window onto ``Window.program``) in a traced run of a tiny
cell on the CPU: exact counts against the program's entry points, the
readers' values, self times within the window, spans per request, and the
readers' arithmetic on hand-built windows."""
import collections
import pathlib
import sys

import pytest

from tinycell import run_tiny

import harness
import specs
import tracereduce
from repro.core.array import ZapRAIDArray
from repro.core.raid import StripeCodec
from repro.integrity import checksum
from repro.obs import hostspans

BENCH = specs.load_benchmark()
# the per-layer metrics that read the program's spans
NEW_NAMES = (
    "stage_ms_per_MiB.write", "bookkeep_ms_per_MiB.write", "media_ms_per_MiB.write",
    "checksum_ms_per_MiB.write", "codec_wait_ms_per_MiB.write",
    "service_self_ms_per_MiB.write", "decode_issue_ms_per_MiB.read",
    "codec_wait_ms_per_MiB.read", "transfer_ms_per_MiB.read", "media_ms_per_MiB.read",
    "service_self_ms_per_MiB.read", "programs_per_decode.read",
    "reconstruct_ms_per_MiB.rebuild", "transfer_ms_per_MiB.rebuild",
    "codec_wait_ms_per_MiB.rebuild", "checksum_ms_per_MiB.rebuild",
    "media_ms_per_MiB.rebuild",
)
NEW = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NEW_NAMES}
# every StripeCodec entry point that issues programs opens one codec:issue
ISSUING = ("encode", "decode", "encode_batch", "decode_batch",
           "encode_batch_async", "decode_batch_async")
MOST_SPANS_PER_REQUEST = 20


def test_every_program_span_is_a_trace_span_name():
    for name in hostspans.SPANS:
        assert tracereduce.SPAN.match(name), name


def test_every_program_span_reader_is_in_the_benchmark():
    assert set(NEW) == set(NEW_NAMES)
    for name, m in NEW.items():
        assert m["source"] in ("program_span", "device_trace")
        mod = specs.load_metric(name)
        # the readers take the spans from the window's accessors
        assert mod.__doc__ and "w.program_" in pathlib.Path(mod.__file__).read_text()


@pytest.fixture
def traced(monkeypatch):
    """Run a traced tiny cell; count calls of the program's entry points
    while the recorder is installed and before the window's snapshot."""
    captured = []
    calls = collections.Counter()
    snapshot = hostspans.HostSpans.snapshot

    def capture(self):
        snap = snapshot(self)
        captured.append(snap)
        return snap

    def counting(key, fn):
        def wrapped(*args, **kw):
            if hostspans.current() is not None and not captured:
                calls[key] += 1
            return fn(*args, **kw)
        return wrapped

    monkeypatch.setattr(hostspans.HostSpans, "snapshot", capture)
    crc = checksum.crc32c_many
    for mod in list(sys.modules.values()):
        if (getattr(mod, "__name__", "").startswith("repro")
                and getattr(mod, "crc32c_many", None) is crc):
            monkeypatch.setattr(mod, "crc32c_many", counting("crc", crc))
    monkeypatch.setattr(ZapRAIDArray, "write",
                        counting("write", ZapRAIDArray.write))
    for name in ISSUING:
        monkeypatch.setattr(StripeCodec, name,
                            counting("issue", getattr(StripeCodec, name)))

    def run(cell):
        r = run_tiny(cell, trace=True)
        assert r["correct"], r["checks"]
        assert hostspans.current() is None   # the window's recorder is gone
        return r, captured[-1]["spans"], calls
    return run


@pytest.mark.parametrize("cell", ("raid5.write.seq128k", "raid5.read.degraded4k",
                                  "raid6.read.degraded4k"))
def test_traced_run_counts_the_program_exactly(traced, cell):
    r, spans, calls = traced(cell)
    count = {name: s["count"] for name, s in spans.items()}
    op = specs.load_cell(cell, trace=True).traffic["op"]
    assert count.get("checksum:crc32c", 0) == calls["crc"]
    assert count.get("codec:issue", 0) == calls["issue"] > 0
    if op == "write":
        assert count["array:stage"] == calls["write"] > 0
    # every new reader of this cell reads a number; the device's needs a chip
    mine = {n for n, m in NEW.items() if cell in m["workloads"]}
    assert mine and all(
        n in r["metrics"] for n in mine if NEW[n]["source"] == "program_span")
    # the program's self times lie inside the traced window
    window_s = r["device"]["window_s"]
    assert all(0.0 <= s["self_s"] <= window_s for s in spans.values())
    assert sum(s["self_s"] for s in spans.values()) <= window_s
    assert count["service:loop"] == 1
    per_request = sum(count.values()) / count["service:arrive"]
    print(f"{cell}: {per_request:.2f} spans per request")
    assert per_request <= MOST_SPANS_PER_REQUEST, (per_request, count)


def test_traced_rebuild_counts_the_program_exactly(traced):
    r, spans, calls = traced("raid6.rebuild.2f")
    count = {name: s["count"] for name, s in spans.items()}
    assert count.get("checksum:crc32c", 0) == calls["crc"] > 0
    assert count.get("codec:issue", 0) == calls["issue"] > 0
    # a pass is one run of the event loop and two rebuild actors, each
    # reconstructing the replaced drive's zone in one batched call
    passes = r["checks"]["rebuild_passes"]["value"]
    assert count["service:loop"] == passes
    assert count["service:handle"] == count["array:reconstruct"] == 2 * passes
    mine = {n for n, m in NEW.items() if "raid6.rebuild.2f" in m["workloads"]}
    assert mine == set(r["metrics"])
    window_s = r["device"]["window_s"]
    assert sum(s["self_s"] for s in spans.values()) <= window_s


def _window(program=None, events=None):
    """A ``harness.Window`` of 2 MiB of every op, with the program spans and
    the device events given."""
    w = harness.Window(cell=None, setup_s=0.0, window_s=1.0, loop=None,
                       block_bytes=4096, stats0={}, stats1={},
                       spans={"self_s": {}, "dispatches": {}}, program=program,
                       trace={"t0_ns": 100, "t1_ns": 200})
    w.mib = lambda op: 2.0
    w.device_events = lambda: events
    return w


def test_readers_find_nothing_without_the_program_s_spans():
    # no recorder in the window: the wrappers' snapshot only
    for w in (_window(), _window(events=[])):
        for name in NEW:
            assert specs.load_metric(name).read(w) is None, name


def test_readers_sum_self_times_per_mib():
    snap = {"spans": {"codec:h2d": {"count": 4, "self_s": 0.25},
                      "codec:d2h": {"count": 4, "self_s": 0.75},
                      "media:read": {"count": 2, "self_s": 0.5},
                      "media:book": {"count": 2, "self_s": 0.125},
                      "service:loop": {"count": 1, "self_s": 1.0},
                      "service:handle": {"count": 9, "self_s": 0.5}},
            "dispatches": {}}
    w = _window(snap)
    read = {n: specs.load_metric(n).read(w) for n in NEW}
    assert read["transfer_ms_per_MiB.read"] == pytest.approx(500.0)
    assert read["media_ms_per_MiB.read"] == pytest.approx(250.0)
    assert read["media_ms_per_MiB.write"] == pytest.approx(312.5)
    assert read["service_self_ms_per_MiB.read"] == pytest.approx(750.0)
    assert read["codec_wait_ms_per_MiB.read"] == 0.0   # never opened
    assert read["programs_per_decode.read"] is None    # no device trace
    assert read["transfer_ms_per_MiB.rebuild"] == pytest.approx(500.0)
    assert read["media_ms_per_MiB.rebuild"] == pytest.approx(312.5)
    assert read["reconstruct_ms_per_MiB.rebuild"] == 0.0


def test_programs_per_decode_counts_module_events_in_the_window():
    snap = {"spans": {"codec:issue": {"count": 4, "self_s": 0.0}},
            "dispatches": {}}
    mod, ops = tracereduce.MODULES_LINE, tracereduce.OPS_LINE
    events = [(mod, "jit_xor_parity", 110, 5), (mod, "jit_squeeze", 120, 5),
              (ops, "parity_xor.1", 111, 2), (mod, "jit_concatenate", 150, 5),
              (mod, "jit_xor_parity", 90, 5), (mod, "jit_stack", 250, 5)]
    w = _window(snap, events)
    assert specs.load_metric("programs_per_decode.read").read(w) == 0.75
