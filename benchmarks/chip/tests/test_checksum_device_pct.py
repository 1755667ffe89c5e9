"""The checksum layer's engagement share (``checksum_device_pct.*``) on
hand-built windows: rows weighted by the ``(N, L)`` of each
``checksum:crc32c`` dispatch key, nothing without a device trace, without
the program's spans, or where the program keys no checksum by its path."""
import pytest

import tinycell  # noqa: F401  (puts the harness on the path)

import harness
import specs

NAMES = ("checksum_device_pct.write", "checksum_device_pct.rebuild")
BENCH = {m["name"]: m for m in specs.load_benchmark()["per_layer"]}


def _window(dispatches, events=((0, "x", 150, 1),)):
    program = None if dispatches is None else {"spans": {}, "dispatches": dispatches}
    w = harness.Window(cell=None, setup_s=0.0, window_s=1.0, loop=None,
                       block_bytes=4096, stats0={}, stats1={},
                       spans={"self_s": {}, "dispatches": {}}, program=program,
                       trace={"t0_ns": 100, "t1_ns": 200})
    w.device_events = lambda: None if events is None else list(events)
    return w


@pytest.mark.parametrize("name", NAMES)
def test_share_weighs_each_call_by_its_rows(name):
    w = _window({("crc32c_device", ((1024, 4096),)): 3,
                 ("crc32c_host", ((1, 4096),)): 40,
                 ("crc32c_host", ((2, 96),)): 4,
                 ("decode", ((2, 1024),)): 7})
    # 3,072 rows on the device of 3,072 + 40 + 8
    assert specs.load_metric(name).read(w) == pytest.approx(100 * 3072 / 3120)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("dispatches,events", [
    ({("crc32c_device", ((1024, 4096),)): 1}, None),   # no device trace
    (None, ((0, "x", 150, 1),)),                       # no program spans
    ({("decode", ((2, 1024),)): 7}, ((0, "x", 150, 1),)),  # a parent's keys
])
def test_share_reads_nothing_where_nothing_is_keyed(name, dispatches, events):
    assert specs.load_metric(name).read(_window(dispatches, events)) is None


@pytest.mark.parametrize("name", NAMES)
def test_share_reads_zero_where_every_row_stayed_on_the_host(name):
    w = _window({("crc32c_host", ((1024, 4096),)): 2})
    assert specs.load_metric(name).read(w) == 0.0
    assert BENCH[name]["source"] == "program_counter"
    assert BENCH[name]["layer"] == "checksum" and BENCH[name]["unit"] == "%"
