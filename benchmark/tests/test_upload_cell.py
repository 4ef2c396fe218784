"""The cell `crs-full-upload.upload-heavy` (ISSUE 34): its files resolve,
the configuration is `crs-full`'s but for what it states about bodies,
the mix is the fixed grid the cell's `why` names, each `sidelane.*`
reader reads a recorded scrape and reads nothing from a program without
the counters, and the whole harness rehearses the cell on CPU.

`data/sidelane_scrape_{before,after}.txt` are two `/metrics` scrapes of a
CPU batcher around 12 `body_post` requests of 4-30 KB (8 of them
rerouted: 4 raw, 4 unpack), cut to the series the readers read.  An
arithmetic fixture: nothing here is a device number.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from generators import body_post
from harness import scrape, work_stream
from test_substage_readers import reader

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
CELL = "crs-full-upload.upload-heavy"
SIDELANE = ("sidelane.request_share", "sidelane.queue_wait_ms",
            "sidelane.scan_ms", "sidelane.confirm_ms",
            "sidelane.waves_per_request", "sidelane.lock_hold_share",
            "sidelane.scan_hbm_roofline")
NEEDS_DEVICE_TRACE = {"device.idle_share", "sidelane.scan_hbm_roofline"}
SECONDS = 2.5


@pytest.fixture(scope="module")
def run_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_cell_resolves(run_module):
    spec = run_module.resolve(REPO / "BENCHMARK.json", CELL)
    assert spec["cell"]["chips"] == 1
    assert set(SIDELANE) <= set(spec["readers"])
    # the accepted share counts batched rows against every scan program
    assert "scan_hbm_roofline" not in spec["readers"]
    assert {m["name"] for m in spec["end_to_end"]} == {
        "verdicts_per_s", "latency_p50_ms", "setup_s"}


@pytest.mark.parametrize("key", ["server_entry", "server_argv",
                                 "sidecar_argv", "reference", "rules",
                                 "scan_words", "chips"])
def test_the_configuration_is_crs_fulls(key):
    one = json.loads((BENCH / "configs" / "crs-full.json").read_text())
    up = json.loads((BENCH / "configs" / "crs-full-upload.json").read_text())
    assert up[key] == one[key]


def test_the_configuration_states_its_body_limits_and_cuts():
    one = json.loads((BENCH / "configs" / "crs-full.json").read_text())
    up = json.loads((BENCH / "configs" / "crs-full-upload.json").read_text())
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = [c for c in bench["configs"] if c["name"] == "crs-full-upload"][0]
    assert entry["source"] == up["source"] and len(up["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(up["reduced"]) == [
        "body_tail", "rules", "scan_words"]
    assert up["body_limits"]["inspected_whole_bytes"] == 131072
    assert up["body_limits"]["ingress_admits_bytes"] == 1 << 20
    assert up["guarantees"][:len(one["guarantees"])] == one["guarantees"]
    assert len(up["guarantees"]) == len(one["guarantees"]) + 3


def test_the_traffic_file_holds_the_parameters_the_issue_names():
    t = json.loads((BENCH / "traffic" / "upload-heavy.json").read_text())
    assert t["generator"] == "body_post"
    assert t["params"] == {"min_body": 2048, "max_body": 131072,
                           "attack_fraction": 0.02}
    assert "max_unpacked_bytes" not in t
    assert t["pool"] == 192
    assert t["loop"] == {"kind": "closed", "in_flight": 4, "connections": 4}
    assert t["control"] == {"kind": "value_head", "bytes": 16384}
    assert t["lead_in_s"] == 2.0 and t["reference_workers"] == 6
    assert t["rehearsal"]["params"] == {
        "min_body": 2048, "max_body": 24576, "attack_fraction": 0.25}
    assert t["rehearsal"]["pool"] == 24
    assert t["rehearsal"]["loop"]["in_flight"] == 2


@pytest.mark.parametrize("seed", [2**31 + 34, 7])
def test_the_pool_is_the_fixed_grid_with_four_payloads(seed):
    t = json.loads((BENCH / "traffic" / "upload-heavy.json").read_text())
    reqs = body_post.generate(seed, t["pool"], t["params"])
    sizes = sorted(len(r.body) for r in reqs)
    # the 192 mid-quantiles of a log-uniform law on [2048, 131072]: 32 an
    # octave, every seed the same sizes in another order
    assert sizes == sorted(body_post._sizes(192, 2048, 131072))
    assert sizes[0] == 2070 and sizes[-1] == 129660
    assert sum(sizes) == 5956438
    for k in range(6):
        octave = [s for s in sizes if 2048 << k <= s < 2048 << (k + 1)]
        assert len(octave) == 32, k
    assert [len(r.body) for r in reqs] != sizes        # shuffled
    payloads = body_post.attack_payloads()
    marked = [r for r in reqs if any(
        enc(p).encode() in r.body for p in payloads
        for enc in (body_post._json_escape, body_post.quote_plus))]
    assert len(marked) == 4
    at_tail = [r for r in marked if any(
        r.body.endswith((enc(p) + tail).encode()) for p in payloads
        for enc, tail in ((body_post._json_escape, '"}'),
                          (body_post.quote_plus, "")))]
    assert len(at_tail) == 1
    # about two thirds of the requests unpack past the batched tiers
    assert 120 <= sum(1 for s in sizes if s > 8500) <= 132


def recorded() -> dict:
    before = scrape.parse_metrics(
        (DATA / "sidelane_scrape_before.txt").read_text())
    after = scrape.parse_metrics(
        (DATA / "sidelane_scrape_after.txt").read_text())
    w = scrape.Window(before, after)
    return {"window": w, "slice": w, "seconds": SECONDS,
            "trace": {"programs": {
                "jit_scan_bytes_jit": {"seconds": 0.25, "count": 132},
                "jit_scan_fold_bucket": {"seconds": 9.0, "count": 40}}},
            "config": {"scan_words": 225},
            "device": {"kind": "TPU v5 lite"}}


RECORDED = {
    "sidelane.request_share": 100.0 * 8 / 12,
    "sidelane.queue_wait_ms": (1008630 - 445564) / 8 / 1e3,
    "sidelane.scan_ms": (3259095 - 1753084) / 8 / 1e3,
    "sidelane.confirm_ms": (255639 - 69640) / 8 / 1e3,
    "sidelane.waves_per_request": (178 - 46) / 8,
    "sidelane.lock_hold_share":
        100.0 * (1414212 - 1277013) / 1e6 / SECONDS,
    # 132 waves, 333 live rows carrying 664,844 B, a pack of 225 words:
    # tokens + 333 x 4 vectors x 900 B + 132 tables of 230,400 B
    "sidelane.scan_hbm_roofline":
        100.0 * ((664844 + 333 * 3600 + 132 * 230400) / 819e9) / 0.25,
}


@pytest.mark.parametrize("name", SIDELANE)
def test_reader_on_the_recorded_scrape(name):
    assert reader(name)(recorded()) == pytest.approx(RECORDED[name])


@pytest.mark.parametrize("name", SIDELANE)
def test_reader_reads_nothing_from_an_empty_window(name):
    """The same scrape twice (no request in the window), and a program
    without the side lane's series (the parent of PR 34)."""
    ctx = recorded()
    ctx["window"] = ctx["slice"] = scrape.Window(ctx["window"].after,
                                                 ctx["window"].after)
    assert reader(name)(ctx) is None
    bare = scrape.parse_metrics("ipt_requests_total 10\n")
    ctx["window"] = ctx["slice"] = scrape.Window(
        bare, scrape.parse_metrics("ipt_requests_total 50\n"))
    assert reader(name)(ctx) is None


def test_roofline_reads_nothing_without_a_trace_or_a_wave_program():
    ctx = dict(recorded(), trace=None)
    assert reader("sidelane.scan_hbm_roofline")(ctx) is None
    ctx = recorded()
    del ctx["trace"]["programs"]["jit_scan_bytes_jit"]
    assert reader("sidelane.scan_hbm_roofline")(ctx) is None


def test_wave_bytes_counts_what_the_rows_needed():
    # one live row of 2,048 B in one wave, a pack of 225 words
    assert work_stream.wave_bytes(1, 2048, 225, 1) == (
        2048 + 4 * 225 * 4 + 256 * 225 * 4)
    with pytest.raises(ValueError):
        work_stream.wave_bytes(1, 2048, 0, 1)


def test_benchmark_json_entries():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "crs-full-upload",
        "traffic": "upload-heavy", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(SIDELANE)
    for m in bench["per_layer"][-7:]:
        assert m["workloads"] == [CELL]
        assert m["layer"] == "oversized side lane"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    common = {m["name"] for m in bench["per_layer"]
              if "crs-full.body-post" in m["workloads"]}
    assert listed - set(SIDELANE) == common - {"scan_hbm_roofline"}


@pytest.mark.slow
def test_rehearsal_of_the_cell():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 34), "--seconds", "6", "--trace", "1"],
        env=dict(os.environ, BENCH_REHEARSAL="1"), cwd=str(REPO),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert line["failed"] == 0
    want = {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]} - NEEDS_DEVICE_TRACE
    v = {k: x["value"] for k, x in line["rehearsal_values"].items()}
    assert set(v) == want
    assert v["dispatch.compiles_in_window"] == 0
    assert 0 < v["sidelane.request_share"] <= 100
    assert v["sidelane.waves_per_request"] >= 8
    assert v["sidelane.scan_ms"] > 0 and v["sidelane.confirm_ms"] > 0
    assert 0 < v["sidelane.lock_hold_share"] <= 100
