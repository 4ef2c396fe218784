"""The whole harness on CPU (`BENCH_REHEARSAL=1`, `--trace 1`) reports
under `rehearsal_values` every per-layer metric that needs no device
trace: the eleven there were and the ten that read the program's
sub-spans and counters (ISSUE 27).  None is a device number."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
NEEDS_DEVICE_TRACE = {"device.idle_share", "scan_hbm_roofline"}


@pytest.mark.slow
def test_rehearsal_prints_every_per_layer_name_that_needs_no_device_trace():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "crs-seclang.api-small"       # the smaller pack: the quicker start
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 91), "--seconds", "3", "--trace", "1"],
        env=dict(os.environ, BENCH_REHEARSAL="1"), cwd=str(REPO),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    want = {m["name"] for m in bench["per_layer"]
            if cell in m["workloads"]} - NEEDS_DEVICE_TRACE
    got = line["rehearsal_values"]
    assert set(got) == want and len(want) == 11 + 10
    assert got["dispatch.compiles_in_window"]["value"] == 0
    # the sub-spans lie inside the stages they open
    v = {k: x["value"] for k, x in got.items()}
    assert (v["dispatch.pack_ms"] + v["dispatch.launch_ms"]
            + v["dispatch.wait_ms"]) <= v["dispatch.scan_stage_ms"]
    assert (v["confirm.walk_ms"] + v["confirm.fold_ms"]
            <= v["confirm.ms_per_dispatch"])
    assert 0 <= v["batcher.loop_busy_share"] <= 100
    assert v["dispatch.launches_per_dispatch"] >= 7
