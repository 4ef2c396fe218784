"""The plain reference walker on hand-made rules and requests: what each
piece of its semantics does, with the expected value worked out by hand."""

import struct

import pytest

from harness.wire import Request, encode_request
from reference import plainwaf as W


def deployment(tmp_path, text, data=None):
    (tmp_path / "crs").mkdir()
    (tmp_path / "data").mkdir()
    (tmp_path / "crs" / "910-test.conf").write_text(text)
    for name, body in (data or {}).items():
        (tmp_path / "data" / name).write_text(body)
    return W.Deployment(tmp_path / "crs")


def request(method="GET", uri="/", headers=None, body=b""):
    frame = encode_request(Request(method, uri, headers or {"host": "h"}, body),
                           req_id=7)
    req_id, req = W.decode_frame(frame)
    assert req_id == 7
    return req


def test_transforms_by_hand():
    assert W.url_decode_uni(b"a+b%20c%u0041%zz%4") == b"a b cA%zz%4"
    assert W.url_decode_uni(b"%c0%a7x") == b"'x"            # overlong folded
    assert W.url_decode_uni(b"%2527") == b"%27"             # one pass only
    assert W.html_entity_decode(b"jav&#x61;s&#99;ript&colon;&amp &lt;") \
        == b"javascript:&amp <"
    assert W.normalize_path(b"/a//b/./c/../d") == b"/a/b/d"
    assert W.cmd_line(b"c^at  /etc/pa'ss'wd ( x") == b"cat/etc/passwd(x"
    assert W.replace_comments(b"un/**/ion sel/*x") == b"un ion sel "
    assert W.transform(b" A%20B ", ["urlDecodeUni", "lowercase",
                                    "removeWhitespace"]) == b"ab"


def test_words_and_actions_of_a_directive():
    words = W._words(r'''SecRule ARGS|&REQUEST_HEADERS:Host "@rx a\"b\\c\d" "id:1,msg:'x, y',t:none"''')
    assert words == ["SecRule", "ARGS|&REQUEST_HEADERS:Host", r'@rx a"b\c\d',
                     "id:1,msg:'x, y',t:none"]
    assert W._actions("id:1,msg:'x, y',t:none,chain") == [
        ("id", "1"), ("msg", "x, y"), ("t", "none"), ("chain", "")]


RULES = r'''
SecAction "id:900110,phase:1,pass,nolog,setvar:tx.inbound_anomaly_score_threshold=5"
SecAction "id:900000,phase:1,pass,nolog,setvar:tx.detection_paranoia_level=2"
SecRule ARGS "@rx (?i)union\s+select" \
    "id:1001,phase:2,block,t:urlDecodeUni,severity:'CRITICAL',tag:'paranoia-level/1'"
SecRule REQUEST_HEADERS:User-Agent "@pmFromFile ../data/agents.txt" \
    "id:1002,phase:1,block,severity:'WARNING',tag:'paranoia-level/2'"
SecRule &REQUEST_HEADERS:Accept "@eq 0" \
    "id:1003,phase:1,pass,severity:'NOTICE',tag:'paranoia-level/2'"
SecRule REQUEST_HEADERS:Content-Length "!@rx ^\d+$" \
    "id:1004,phase:1,deny,severity:'NOTICE',tag:'paranoia-level/1'"
SecRule REQUEST_METHOD "@streq POST" "id:1005,phase:2,block,chain,severity:'ERROR',tag:'paranoia-level/1'"
    SecRule ARGS_POST "@rx select" "t:lowercase,chain"
    SecRule MATCHED_VAR "@rx from"
SecRule REQUEST_BODY "@rx <script" "id:1006,phase:2,block,severity:'CRITICAL',tag:'paranoia-level/3'"
SecRule REQUEST_BODY "@rx evil" "id:1007,phase:2,block,severity:'CRITICAL',tag:'paranoia-level/1'"
'''


def test_rules_variables_chains_and_scoring(tmp_path):
    dep = deployment(tmp_path, RULES, {"agents.txt": "# list\nsqlmap\nNikto\n"})
    assert (dep.threshold, dep.paranoia) == (5, 2)
    assert [r.rule_id for r in dep.served] == [1001, 1002, 1003, 1004, 1005, 1007]

    def ids(**kw):
        return W.verdict(dep, request(**kw))

    ok = {"host": "h", "accept": "*/*"}
    assert ids(uri="/?q=shoes", headers=ok) == (False, False, [])
    # one CRITICAL rule reaches the threshold; double encoding decodes once
    # in the parser and once in the rule's own transform
    assert ids(uri="/?q=1+UNION%2520SELECT+x", headers=ok) == (True, True, [1001])
    # a WARNING (3) and a NOTICE (2) add up to 5
    assert ids(headers={"host": "h", "user-agent": "NIKTO/2"}) \
        == (True, True, [1002, 1003])
    # alone the NOTICE is no attack; a variable that is absent runs nothing
    assert ids() == (False, False, [1003])
    # a matched deny blocks below the threshold; negation runs per value
    assert ids(headers=dict(ok, **{"content-length": "1x"})) \
        == (False, True, [1004])
    form = {"content-type": "application/x-www-form-urlencoded"}
    # the chain's last link looks at the value its second link matched
    assert ids(method="POST", headers=dict(ok, **form),
               body=b"a=SELECT+1&b=from")[2] == []
    # ... as that link transformed it: 'FROM' was lowercased there
    assert ids(method="POST", headers=dict(ok, **form),
               body=b"a=x&b=SELECT+1+FROM+t")[2] == [1005]
    assert ids(method="GET", uri="/?b=select+1+from+t", headers=ok)[2] == []


def test_json_bodies_feed_args_and_the_unpacked_body(tmp_path):
    dep = deployment(tmp_path, RULES, {"agents.txt": "x\n"})
    hdr = {"host": "h", "accept": "*/*", "content-type": "application/json"}
    req = request("POST", "/", hdr,
                  b'{"a": {"b": ["select", 1, true, null]}, "t": "\\u0065vil"}')
    var = W.Variables(req)
    assert var.collection("post") == [
        (b"json.a.b", b"select"), (b"json.a.b", b"1"), (b"json.a.b", b"true"),
        (b"json.a.b", b""), (b"json.t", b"evil")]
    assert var.unpacked_body() == req.body + b"\x1f" + b"\x1f".join(
        [b"a", b"b", b"select", b"t", b"evil"])
    # the escape hides 'evil' from the raw bytes; the unpacked body shows it
    assert W.verdict(dep, req)[2] == [1007]
    # a control that sees only the head of a value misses it
    assert W.verdict(dep, req, value_head=20)[2] == []


def test_what_is_not_modelled_is_refused(tmp_path):
    with pytest.raises(W.NotModelled):
        deployment(tmp_path, 'SecRule ARGS "@geoLookup" "id:1,phase:2,block"')
    dep = W.Deployment.__new__(W.Deployment)
    dep.served, dep.threshold = [], 5
    with pytest.raises(W.NotModelled):
        W.verdict(dep, request("POST", "/", {"content-encoding": "gzip"}, b"x"))
    with pytest.raises(W.NotModelled):
        W.decode_frame(b"RTPI" + struct.pack("<I", 0))


def test_strict_grammar_operators():
    for attack in (b"1' UNION SELECT a, b FROM users--", b"1 OR 1=1",
                   b"' OR 'a'='a", b"1; DROP TABLE orders;--", b"admin'--",
                   b"1' AND SLEEP(5)--"):
        assert W.detect_sqli(attack), attack
    for benign in (b"select the best option from the union of both lists",
                   b"q=o", b"rock and roll", b"o'brien", b"src/**/lib or docs/**/api"):
        assert not W.detect_sqli(benign), benign
    assert W.detect_sqli(b"x" * 4090 + b" OR 1=1") is False    # past the window
    for attack in (b"<script>alert(1)</script>", b"<img src=x onerror=alert(1)>",
                   b"javascript:alert(1)", b"&#x3c;script"):
        assert W.detect_xss(attack), attack
    assert not W.detect_xss(b"I like cats <3 and dogs")
