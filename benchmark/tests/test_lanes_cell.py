"""The four-lane cell (`crs-full-4lane.api-small-x4`): its files resolve,
the configuration differs from `crs-full` in the one argument the
deployment differs in, and the whole harness rehearses it on four virtual
CPU devices, reporting the `lanes.*` metrics beside the rest.  None is a
device number."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
CELL = "crs-full-4lane.api-small-x4"
LANES = {"lanes.request_share_min", "lanes.scan_overlap_share",
         "lanes.serial_host_share", "lanes.share_scan_ms"}
NEEDS_DEVICE_TRACE = {"device.idle_share", "scan_hbm_roofline"}


@pytest.fixture(scope="module")
def run_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_cell_resolves(run_module):
    spec = run_module.resolve(REPO / "BENCHMARK.json", CELL)
    assert spec["cell"]["chips"] == 4
    assert spec["traffic"]["loop"] == {
        "kind": "closed", "in_flight": 128, "connections": 8}
    assert spec["traffic"]["pool"] == 16384
    assert LANES <= set(spec["readers"])
    assert {m["name"] for m in spec["end_to_end"]} == {
        "verdicts_per_s", "latency_p50_ms", "setup_s"}
    assert (REPO / spec["config"]["server_entry"]).is_file()


def test_the_configuration_is_crs_full_but_for_the_lanes():
    one = json.loads((BENCH / "configs" / "crs-full.json").read_text())
    four = json.loads((BENCH / "configs" / "crs-full-4lane.json").read_text())
    argv = list(one["server_argv"])
    at = argv.index("--lanes")
    assert argv[at + 1] == "1"
    argv[at + 1] = "auto"
    assert four["server_argv"] == argv
    for key in ("sidecar_argv", "reference", "rules", "scan_words", "reduced"):
        assert four[key] == one[key], key
    assert four["guarantees"][:len(one["guarantees"])] == one["guarantees"]


def test_the_mix_is_api_small_at_four_times_the_load():
    one = json.loads((BENCH / "traffic" / "api-small.json").read_text())
    four = json.loads((BENCH / "traffic" / "api-small-x4.json").read_text())
    for key in ("generator", "params", "control", "lead_in_s"):
        assert four[key] == one[key], key
    assert four["loop"]["in_flight"] == 4 * one["loop"]["in_flight"]
    assert four["pool"] == 4 * one["pool"]


@pytest.mark.slow
def test_rehearsal_of_the_cell_on_four_cpu_devices():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 93), "--seconds", "3", "--trace", "1"],
        env=dict(os.environ, BENCH_REHEARSAL="1"), cwd=str(REPO),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert line["failed"] == 0 and line["device"]["count"] == 4
    want = {m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"]} - NEEDS_DEVICE_TRACE
    v = {k: x["value"] for k, x in line["rehearsal_values"].items()}
    assert set(v) == want and LANES <= want
    assert v["dispatch.compiles_in_window"] == 0
    assert 0 < v["lanes.request_share_min"] <= 100
    assert 0 <= v["lanes.scan_overlap_share"] <= 100
    assert 0 < v["lanes.serial_host_share"] <= 100
    assert v["lanes.share_scan_ms"] > 0
