"""The benchmark's data, its loading by name, the closed loop's counting and
the metric arithmetic, on the CPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from tinycell import CHIP, ROOT, TINY_MOST, TINY_TRAFFIC, run_tiny

import harness
import reference
import specs
from loadgen import AddressStream, ClosedLoop, PayloadSource
from reference import BlockReference

BENCH = specs.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E_KEYS = {"name", "unit", "better", "bound", "source", "workloads"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves", "workloads"}
CELL_NAMES = [w["name"] for w in BENCH["workloads"]]


def _one_line(text, most=200):
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELL_NAMES)
def test_every_cell_loads_by_name(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] == 1 and _one_line(w["why"])
    assert any(c["name"] == w["config"] for c in BENCH["configs"])
    for trace in (False, True):
        c = specs.load_cell(cell, trace=trace)
        assert c.config["name"] == w["config"]
        assert c.traffic["op"] in ("read", "write", "rebuild")
        assert callable(c.runner.prepare) and callable(c.runner.check)
        assert c.metrics, "every cell reports metrics in both modes"
    names = {n for n, _, _ in specs.metrics_for(BENCH, cell, False)}
    assert "setup_s" in names and len(names) >= 2


def test_configs_name_existing_files_that_state_their_cuts():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/") and c["file"] not in seen
        seen.add(c["file"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert data["guarantee"]["drive_losses_survived"] >= 1
        assert _one_line(c["source"]) and _one_line(c["why"])
        for key in c["reduced"]:
            assert NAME.match(key)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_names_units_and_entry_keys():
    groups = [BENCH["configs"], BENCH["workloads"],
              BENCH["end_to_end"] + BENCH["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in BENCH["end_to_end"]:
        assert set(m) <= E2E_KEYS and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= LAYER_KEYS and UNIT.match(m["unit"])
        assert _one_line(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_files_agree_with_the_benchmark(metric):
    mod = specs.load_metric(metric["name"])
    assert mod.UNIT == metric["unit"] and mod.SOURCE == metric["source"]
    if "layer" in metric:
        assert mod.LAYER == metric["layer"] and mod.MOVES == metric["moves"]


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_cell_of_a_layer_metric_reports_what_it_moves(metric):
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    moves = e2e[metric["moves"]]
    for cell in metric.get("workloads", CELL_NAMES):
        assert cell in moves.get("workloads", CELL_NAMES), (metric["name"], cell)
    # a .write metric only in write cells, a .read metric only in read
    # cells, a .rebuild metric only in rebuild cells
    op = metric["name"].rsplit(".", 1)[-1]
    for cell in metric["workloads"]:
        traffic = specs.load_cell(cell, trace=True).traffic
        assert op not in ("read", "write", "rebuild") or traffic["op"] == op


# -- the closed loop and the metric arithmetic ------------------------------


class FakeEngine:
    def __init__(self):
        self.queue = []

    def run(self):
        while self.queue:
            self.queue.pop(0)()


class FakeReq:
    def __init__(self, ok, result=None):
        self._ok, self.result = ok, result

    def ok(self):
        return self._ok


class FakeService:
    """Completes requests in submission order, one clock tick each; every
    fifth request fails."""

    def __init__(self, clock):
        self.engine = FakeEngine()
        self.clock = clock
        self.n = 0
        self.cq = type("CQ", (), {"drain": lambda self: []})()

    def _complete(self, cb, result=None):
        self.n += 1
        ok = self.n % 5 != 0

        def fire():
            self.clock.t += 1.0
            cb(FakeReq(ok, result))
        self.engine.queue.append(fire)

    def submit_write(self, tenant, lba, data, cb):
        self._complete(cb)

    def submit_read(self, tenant, lba, n, cb):
        self._complete(cb, np.zeros((n, 64), np.uint8))

    def drain(self):
        self.engine.run()


class FakeClock:
    t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("op", ("read", "write"))
def test_closed_loop_counts_attempted_failed_and_the_window(op):
    clock = FakeClock()
    svc = FakeService(clock)
    rng = np.random.default_rng(0)
    ref = BlockReference(64, 64)
    loop = ClosedLoop(svc, "t", op, 2, 4, AddressStream("uniform", 64, 2, rng),
                      ref, PayloadSource(64, rng) if op == "write" else None,
                      clock=clock)
    loop.run(10.0)
    # a completion at t >= 10 closes the loop; the 4 in flight then drain
    assert loop.t_close == 10.0 and len(loop.samples) == 13
    assert sum(not s.ok for s in loop.samples) == 2
    done = loop.in_window(op)
    assert len(done) == 8   # 10 completions by t = 10, the 5th and 10th failed
    w = harness.Window(cell=None, setup_s=1.0, window_s=10.0, loop=loop,
                       block_bytes=harness.MiB // 4, stats0={}, stats1={})
    assert w.mib(op) == pytest.approx(8 * 2 / 4)
    lat = w.latencies_ms(op)
    assert lat.size == 8 and w.percentile_ms(op, 99) == lat.max()
    assert w.percentile_ms(op, 50) == lat[3]


def test_percentile_is_nearest_rank_over_every_request():
    w = harness.Window(cell=None, setup_s=0, window_s=1, loop=None,
                       block_bytes=4096, stats0={}, stats1={})
    w.latencies_ms = lambda op: np.arange(1, 201, dtype=float)
    assert w.percentile_ms("read", 99) == 198.0
    assert w.percentile_ms("read", 100) == 200.0


def test_address_streams_are_seeded_and_in_range():
    a = AddressStream("seq", 1000, 32, np.random.default_rng(7))
    b = AddressStream("seq", 1000, 32, np.random.default_rng(7))
    xs = [a.next() for _ in range(100)]
    assert xs == [b.next() for _ in range(100)]
    assert all(0 <= x <= 1000 - 32 for x in xs)
    assert all((y - x) % (1000 - 31) == 32 for x, y in zip(xs, xs[1:]))
    u = AddressStream("uniform", 1000, 1, np.random.default_rng(1))
    assert all(0 <= u.next() < 1000 for _ in range(5000))


@pytest.mark.parametrize("message,crc", [
    (b"123456789", 0xE3069283),          # the CRC-32C check value
    (bytes(32), 0x8A9136AA),             # RFC 3720 B.4: 32 bytes of zeros
    (bytes(range(32)), 0x46DD794E),      # RFC 3720 B.4: 0x00 .. 0x1f
])
def test_reference_crc32c_vectors(message, crc):
    rows = np.frombuffer(message, np.uint8)[None]
    assert int(reference.crc32c_rows(rows)[0]) == crc


def test_payloads_regenerate_from_their_stamps():
    p = PayloadSource(64, np.random.default_rng(5))
    made = [p.make(8) for _ in range(40)]
    i, j = np.array([0, 17, 39]), np.array([0, 5, 7])
    again = p.blocks(i, j, 8)
    assert all((again[k] == made[i[k]][j[k]]).all() for k in range(3))
    gi, gj = p.stamps(again)
    assert gi.tolist() == i.tolist() and gj.tolist() == j.tolist()


def test_payloads_never_repeat():
    p = PayloadSource(64, np.random.default_rng(3))
    rows = np.concatenate([p.make(32) for _ in range(200)])
    assert np.unique(rows, axis=0).shape[0] == rows.shape[0]


@pytest.mark.parametrize("cell", ("raid5.write.seq128k", "raid6.read.degraded4k"))
def test_traced_run_reports_its_layer_metrics(cell):
    r = run_tiny(cell, trace=True, seconds=2.0)
    assert r["correct"], r["checks"]
    got = set(r["metrics"])
    assert any(n.startswith("service_ms_per_MiB") for n in got)
    assert any(n.startswith("codec_ms_per_MiB") for n in got)
    # no chip: no device metric is read from a CPU trace
    assert not any("roofline" in n or "idle" in n for n in got)
    assert list(r)[-1] == "checks"


def test_untraced_run_reports_its_end_to_end_metrics():
    r = run_tiny("raid5.read.degraded4k")
    assert set(r["metrics"]) == {"read_MiBps", "read_p99_ms", "setup_s"}
    assert r["metrics"]["read_MiBps"]["unit"] == "MiB/s"
    assert r["attempted"] >= len(r["metrics"]) and r["failed"] == 0


def _child(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_cli_refuses_a_cpu():
    p = _child(ROOT, "--workload", "raid5.write.seq128k", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_cli_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _child(tmp_path, "--workload", "raid5.write.seq128k", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""


# -- configurations pass through whole ---------------------------------------


def _old_build(config, traffic):
    """The array and drive configuration as the harness built them from a
    fixed list of keys, before the configuration passed through whole."""
    from repro.core.array import ZapRaidConfig
    from repro.core.raid import make_scheme
    from repro.core.segment import solve_stripes_per_segment
    from repro.core.zns import ZnsConfig

    bb = config["block_bytes"]
    volume_blocks = traffic["volume_mib"] * harness.MiB // bb
    k = make_scheme(config["scheme"], config["n_drives"]).k
    stripes, _ = solve_stripes_per_segment(
        config["zone_cap_blocks"], config["chunk_blocks"], bb)
    n_zones = -(-3 * volume_blocks // (2 * k * stripes)) + 2
    cfg = ZapRaidConfig(
        scheme=config["scheme"], n_drives=config["n_drives"],
        group_size=config["group_size"], chunk_blocks=config["chunk_blocks"],
        logical_blocks=volume_blocks,
        gc_free_segments_low=config["gc_free_segments_low"],
        batched=config["batched"], verify_reads=config["verify_reads"],
        append_order=config["append_order"],
    )
    zns = ZnsConfig(n_zones=n_zones, zone_cap_blocks=config["zone_cap_blocks"],
                    block_bytes=bb, max_open_zones=config["max_open_zones"])
    return cfg, zns


@pytest.mark.parametrize("cell", CELL_NAMES)
def test_configs_build_the_arrays_they_built(cell):
    c = specs.load_cell(cell, trace=False)
    cfg, zns = harness.array_configs(c.config, c.traffic)
    assert (cfg, zns) == _old_build(c.config, c.traffic)
    assert zns.n_zones == 3


def test_an_unknown_config_key_is_refused(tmp_path, monkeypatch):
    config = specs.load_json("configs", "raid5-3p1")
    assert specs.config_parts(config)[1] == {
        "zone_cap_blocks": 275712, "block_bytes": 4096, "max_open_zones": 14}
    for bad in ("hybird", "logical_blocks", "n_zones"):
        with pytest.raises(ValueError, match=bad):
            specs.config_parts(dict(config, **{bad: 1}))
    # refused as the cell loads, before anything is built
    monkeypatch.setattr(specs, "load_json", lambda kind, name: (
        dict(config, hybird=True) if kind == "configs" else
        json.loads((CHIP / kind / f"{name}.json").read_text())))
    with pytest.raises(ValueError, match="hybird"):
        specs.load_cell("raid5.write.seq128k", trace=False)


def test_a_hybrid_configuration_is_a_file_alone(monkeypatch):
    from repro.core.segment import SegmentClass

    load = specs.load_json

    def hybrid(kind, name):
        data = load(kind, name)
        if kind == "configs":
            # GC keeps a zone free for each open segment, which a drive
            # failure reopens at survivor width
            data.update(hybrid=True, n_small=1, n_large=1,
                        small_chunk_blocks=1, large_chunk_blocks=4,
                        gc_free_segments_low=2)
        return data

    monkeypatch.setattr(specs, "load_json", hybrid)
    built = []
    monkeypatch.setattr(harness, "build", lambda *a, _b=harness.build: (
        built.append(_b(*a)) or built[-1]))
    r = run_tiny("raid5.write.seq128k", seconds=1.0)
    assert r["correct"], r["checks"]
    arr = built[0].arr
    assert arr.cfg.hybrid and arr.cfg.n_large == 1 and arr.large_ids
    classes = {rec.info.seg_class for rec in arr.segments.values()}
    assert int(SegmentClass.LARGE) in classes
    # the window's requests are large enough to go to the large segments
    assert TINY_MOST["request_blocks"] >= arr.cfg.large_chunk_blocks


# -- the rebuild cell ----------------------------------------------------------

REBUILD = "raid6.rebuild.2f"


def test_rebuild_cell_is_correct_and_reports_its_rate():
    r = run_tiny(REBUILD)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"rebuild_MiBps", "setup_s"}
    assert r["metrics"]["rebuild_MiBps"]["value"] > 0
    checks = r["checks"]
    assert checks["rebuild_passes"]["value"] == r["attempted"] >= 1
    # each pass leaves both replaced drives holding the prefilled zone: a
    # header and one chunk a stripe of two data chunks
    volume_blocks = TINY_TRAFFIC["volume_mib"] * harness.MiB // 4096
    per_pass = checks["rebuilt_blocks"]["value"] / r["attempted"]
    assert per_pass == 2 * (1 + volume_blocks // 2)


def test_rebuild_cell_traced_reports_its_layer_metrics():
    r = run_tiny(REBUILD, trace=True)
    assert r["correct"], r["checks"]
    want = {m["name"] for m in BENCH["per_layer"]
            if REBUILD in m.get("workloads", []) and m["source"] == "program_span"}
    assert want and set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())


# -- the four block cells read as before --------------------------------------

BLOCK_E2E = {"write": {"write_MiBps", "setup_s"},
             "read": {"read_MiBps", "read_p99_ms", "setup_s"}}
BLOCK_TRACED = {
    "write": {"service_ms_per_MiB.write", "array_ms_per_MiB.write",
              "write_amp.write", "crc_ms_per_MiB.write", "codec_ms_per_MiB.write",
              "stage_ms_per_MiB.write", "bookkeep_ms_per_MiB.write",
              "media_ms_per_MiB.write", "checksum_ms_per_MiB.write",
              "codec_wait_ms_per_MiB.write", "service_self_ms_per_MiB.write"},
    "read": {"service_ms_per_MiB.read", "array_ms_per_MiB.read",
             "codec_ms_per_MiB.read", "h2d_copies_per_MiB.read",
             "decode_issue_ms_per_MiB.read", "codec_wait_ms_per_MiB.read",
             "transfer_ms_per_MiB.read", "media_ms_per_MiB.read",
             "service_self_ms_per_MiB.read"},
}
BLOCK_CHECKS = {
    "write": ["failed_requests", "compiles_in_window", "unpersisted_acked_blocks",
              "readback_mismatched_blocks", "crc_mismatched_blocks",
              "degraded_mismatched_blocks", "degraded_blocks_decoded"],
    "read": ["failed_requests", "compiles_in_window", "read_mismatched_blocks",
             "window_degraded_reads"],
}
BLOCK_LIMITS = {"degraded_blocks_decoded": (1, ">="),
                "window_degraded_reads": (1, ">=")}
BLOCK_CELLS = ("raid5.write.seq128k", "raid5.read.degraded4k",
               "raid6.write.seq128k", "raid6.read.degraded4k")


@pytest.mark.parametrize("cell", BLOCK_CELLS)
@pytest.mark.parametrize("trace", (False, True))
def test_block_cells_report_the_names_they_reported(cell, trace):
    op = cell.split(".")[1]
    r = run_tiny(cell, trace=trace)
    assert r["correct"], r["checks"]
    # the CPU reads no device metric: those need a chip trace
    assert set(r["metrics"]) == (BLOCK_TRACED if trace else BLOCK_E2E)[op]
    assert list(r["checks"]) == BLOCK_CHECKS[op]
    for name, c in r["checks"].items():
        assert (c["limit"], c["rule"]) == BLOCK_LIMITS.get(name, (0, "<="))
