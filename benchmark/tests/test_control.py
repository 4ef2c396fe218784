"""The control has to come out as not correct.

The control is the reference put in the program's place with one stated
guarantee broken (the traffic file's `control`): a thinner pack at
paranoia level 1, or only the head of every value examined.  Here at a size a
test run can hold: both walks of a small pool, compared exactly as a
run compares served verdicts.  The chip-sized readings are in PERF.md.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
POOL = {"api-small": 400, "body-post": 96}


def walk(tmp_path, mix, control):
    traffic = BENCH / "traffic" / (mix + ".json")
    out = tmp_path / ("control.json" if control else "reference.json")
    cmd = [sys.executable, str(BENCH / "reference" / "walk.py"),
           "--traffic", str(traffic), "--seed", "2147483999",
           "--pool", str(POOL[mix]), "--rules-dir", str(BENCH / "rules" / "crs"),
           "--out", str(out)]
    if control:
        cmd += ["--control", json.dumps(control)]
    return subprocess.Popen(cmd, cwd=str(tmp_path)), out


@pytest.mark.slow
@pytest.mark.parametrize("mix", sorted(POOL))
def test_the_control_differs_from_the_reference(tmp_path, mix):
    control = json.loads(
        (BENCH / "traffic" / (mix + ".json")).read_text())["control"]
    procs = [walk(tmp_path, mix, None), walk(tmp_path, mix, control)]
    for p, _out in procs:
        assert p.wait(timeout=600) == 0
    ref, ctl = (json.loads(out.read_text()) for _p, out in procs)
    assert set(ref) == set(ctl) and len(ref) == POOL[mix]
    mismatched = sum(1 for k in ref if ref[k] != ctl[k])
    assert mismatched > 0
    assert sum(1 for v in ref.values() if v[0]) > 0    # it saw attacks


@pytest.mark.slow
def test_a_body_that_would_leave_the_batched_path_is_refused(tmp_path):
    """`max_unpacked_bytes`: the mix's bodies stay under the side-lane
    threshold once unpacked; a pool entry over it stops the walk."""
    traffic = json.loads((BENCH / "traffic" / "body-post.json").read_text())
    assert traffic["max_unpacked_bytes"] == 16384
    traffic["max_unpacked_bytes"] = 4096      # the largest bodies now exceed it
    (tmp_path / "t.json").write_text(json.dumps(traffic))
    r = subprocess.run(
        [sys.executable, str(BENCH / "reference" / "walk.py"), "--traffic",
         str(tmp_path / "t.json"), "--seed", "5", "--pool", "32",
         "--rules-dir", str(BENCH / "rules" / "crs"),
         "--out", str(tmp_path / "o.json")],
        cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600)
    assert r.returncode != 0
    assert "would leave the batched path" in r.stderr
    assert not (tmp_path / "o.json").exists()
