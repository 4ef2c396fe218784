"""A whole run of `run.py` with the timed path broken underneath.

The harness's look for a chip is skipped (`BENCH_REHEARSAL=1`: CPU, tiny
sizes); everything else is the real run: real server, real sidecar,
reference children, lead-in, two scrapes, comparison with the plain
reference.  Of the faults a
cell can have, this system has one: an answer altered where it is
produced (there is no training state, no batch mean and, on one chip, no
exchange between chips).  `correct` has to come out false; the same run
on the unbroken server has to come out true with a well-formed line that
names the CPU and carries no metric value.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
CELL = "crs-seclang.api-small"     # the smaller pack: the quicker start


def run_cell(tmp_path, server_entry=None, trace=0):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    if server_entry:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        config = json.loads((REPO / entry["file"]).read_text())
        config["server_entry"] = server_entry
        (tmp_path / "config.json").write_text(json.dumps(config))
        entry["file"] = os.path.relpath(tmp_path / "config.json", REPO)
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 77), "--seconds", "2", "--trace", str(trace),
         "--benchmark-file", str(bench_file)],
        env=dict(os.environ, BENCH_REHEARSAL="1"), cwd=str(REPO),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1]), r.stderr


@pytest.mark.slow
def test_an_altered_answer_is_not_correct(tmp_path):
    line, err = run_cell(tmp_path, "benchmark/tests/broken_served.py")
    assert line["correct"] is False
    assert line["checks"]["mismatched"]["value"] > 0
    assert line["checks"]["lost_or_doubled"]["value"] == 0
    # each number compared stands beside its limit in the last lines
    assert [ln.split()[0] for ln in err.splitlines()[-4:]] == [
        "mismatched", "lost_or_doubled", "unflagged_fallbacks", "compared_min"]


@pytest.mark.slow
def test_the_unbroken_rehearsal_is_correct_and_names_the_cpu(tmp_path):
    line, _err = run_cell(tmp_path, trace=1)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # a CPU timing is never written under a metric's name
    assert line["metrics"] == {}
    assert "dispatch.compiles_in_window" in line["rehearsal_values"]
    assert "device.idle_share" not in line["rehearsal_values"]
    assert "scan_hbm_roofline" not in line["rehearsal_values"]
