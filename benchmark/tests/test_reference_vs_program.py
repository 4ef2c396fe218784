"""A second witness for the plain reference: the program's own CPU path.

The reference shares no code with the program, so the two can be set
against each other: the program's confirm-only walk on CPU
(`DetectionPipeline.detect_cpu_only`, no device, no prefilter) and
`reference/plainwaf.py` have to give the same verdict for every request
of a pool from each mix, at an attack share far above the cells' 2% so
that every payload and placement of the generators' tables is met.
The program is imported here as the system under test, nowhere else.
"""

import json
import os
from pathlib import Path

import pytest

from harness.wire import encode_request
from reference import plainwaf
from reference.walk import load_generator

BENCH = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def program():
    os.environ["IPT_NO_NATIVE_CONFIRM"] = "1"
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import load_seclang_dir
    from ingress_plus_tpu.models.pipeline import DetectionPipeline

    rules = load_seclang_dir(str(BENCH / "rules" / "crs"))
    return DetectionPipeline(compile_ruleset(rules), mode="block",
                             fail_open=False)


@pytest.mark.parametrize("mix,n", [("api-small", 600), ("body-post", 60)])
def test_the_program_on_cpu_and_the_reference_agree(program, mix, n):
    from ingress_plus_tpu.serve.protocol import decode_request

    traffic = json.loads((BENCH / "traffic" / (mix + ".json")).read_text())
    params = dict(traffic["params"], attack_fraction=0.5)
    reqs = load_generator(traffic["generator"]).generate(2**31 + 11, n, params)
    frames = [encode_request(r, req_id=i) for i, r in enumerate(reqs)]
    dep = plainwaf.Deployment(BENCH / "rules" / "crs")
    decoded = []
    for frame in frames:
        _id, mode, req = decode_request(frame[8:])
        req.mode = mode
        decoded.append(req)
    attacks = 0
    for at in range(0, n, 16):
        served = program.detect_cpu_only(decoded[at:at + 16])
        for frame, v in zip(frames[at:at + 16], served):
            idx, request = plainwaf.decode_frame(frame)
            want = plainwaf.verdict(dep, request)
            have = (bool(v.attack), bool(v.blocked),
                    sorted(int(r) for r in v.rule_ids))
            assert have == (want[0], want[1], sorted(want[2])), \
                "pool entry %d: %r" % (idx, reqs[idx])
            attacks += want[0]
    assert attacks > n // 4
