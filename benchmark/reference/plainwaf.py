"""The plain reference: a rule walker that owns its semantics.

For every request it evaluates every rule of the deployment, one
(request, rule) pair at a time, with Python `re` and plain byte
operations: no prefilter, no device, no batching, and no line of the
program under test (a request remembers its transformed values, since
most rules share a transform chain: nothing else is cached).  It reads the deployment's rule text
(`benchmark/rules/`: SecLang `.conf` files, their word lists, and the
signature packs as data), decodes the wire frame itself, builds the
request's variables itself, and applies transforms and operators itself.

What it implements is the deployment's *stated* semantics (the SecLang
subset of `rules/README.md`, the unpack stage and the scoring as the
program's documentation states them), written independently.  Anything a
rule or a request uses that is not modelled here raises `NotModelled`:
the walker refuses, it never guesses.  Stated rules of this deployment
that differ from stock ModSecurity, all implemented here on purpose:

* a scalar variable that is empty is absent (no operator runs on it);
* `REQUEST_BODY` is the unpacked body: its bytes, then the keys and
  strings of a JSON document in the document's order, then a whole-body
  base64 decode, joined by the unit separator 0x1f (a compressed, XML,
  protobuf or multipart body is not modelled);
* JSON bodies feed `ARGS_POST` under dotted names (`json.a.b`), arrays
  repeating the parent's name;
* url decoding also folds overlong UTF-8 encodings of one byte;
* every matched rule scores its severity (CRITICAL 5, ERROR 4, WARNING 3,
  NOTICE 2) whatever its action; a request is an attack when the sum
  reaches the inbound threshold, and is blocked when it is an attack or
  a matched rule's action is `deny`;
* the strict-grammar operators look at the first 4,096 bytes of a value;
* a signature-pack rule looks at whole streams: `args` is the query
  string url-decoded once, `headers` is the `name: value` units joined
  by 0x1f.
"""

from __future__ import annotations

import base64
import binascii
import ipaddress
import json
import re
import struct
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

SEP = b"\x1f"
SEVERITY_SCORE = {"CRITICAL": 5, "ERROR": 4, "WARNING": 3, "NOTICE": 2,
                  "INFO": 1, "DEBUG": 1}
DETECT_WINDOW = 4096


class NotModelled(Exception):
    """A rule or a request needs something this walker does not model."""


# --------------------------------------------------------------- wire

_REQ_HEAD = struct.Struct("<QIBB III")


class HttpRequest:
    def __init__(self, method: str, uri: bytes, headers: List[Tuple[bytes, bytes]],
                 body: bytes, mode: int = 2):
        self.method, self.uri, self.headers = method, uri, headers
        self.body, self.mode = body, mode


def decode_frame(frame: bytes) -> Tuple[int, HttpRequest]:
    """One request frame of the sidecar's socket -> (req_id, request)."""
    if frame[:4] != b"QTPI":
        raise NotModelled("not a request frame: %r" % frame[:4])
    (length,) = struct.unpack_from("<I", frame, 4)
    payload = frame[8:8 + length]
    req_id, _tenant, mode, m_len, uri_len, hdr_len, body_len = \
        _REQ_HEAD.unpack_from(payload)
    if mode != 2:
        raise NotModelled("mode byte %d: only plain block mode (2)" % mode)
    at = _REQ_HEAD.size
    method = payload[at:at + m_len].decode("ascii")
    at += m_len
    uri = payload[at:at + uri_len]
    at += uri_len
    units = payload[at:at + hdr_len]
    at += hdr_len
    body = payload[at:at + body_len]
    if at + body_len != len(payload):
        raise NotModelled("frame length does not add up")
    headers: Dict[bytes, bytes] = {}
    for unit in (units.split(SEP) if units else ()):
        name, _, value = unit.partition(b": ")
        if name:
            if name.lower() == b"x-detect-tpu-client-ip":
                raise NotModelled("trusted client-ip header")
            headers[name] = value       # a repeated name keeps the last value
    return req_id, HttpRequest(method, uri, list(headers.items()), body, mode)


# --------------------------------------------------------- transforms

def _fold_overlong(data: bytes) -> bytes:
    """C0/C1 xx and E0 8x/9x xx encodings of a code point under 256."""
    if not (b"\xc0" in data or b"\xc1" in data or b"\xe0" in data):
        return data
    out = bytearray()
    i = 0
    while i < len(data):
        b = data[i]
        nxt = data[i + 1] if i + 1 < len(data) else -1
        if b in (0xC0, 0xC1) and 0x80 <= nxt <= 0xBF:
            out.append(((b & 0x1F) << 6) | (nxt & 0x3F))
            i += 2
        elif (b == 0xE0 and 0x80 <= nxt <= 0x9F and i + 2 < len(data)
              and 0x80 <= data[i + 2] <= 0xBF
              and (((nxt & 0x3F) << 6) | (data[i + 2] & 0x3F)) < 0x100):
            out.append(((nxt & 0x3F) << 6) | (data[i + 2] & 0x3F))
            i += 3
        else:
            out.append(b)
            i += 1
    return bytes(out)


_ESCAPE = re.compile(rb"%(?:[uU]([0-9a-fA-F]{4})|([0-9a-fA-F]{2}))")


def url_decode_uni(data: bytes) -> bytes:
    """`+` to space, %XX, %uXXXX (the low byte of its code), one pass;
    an invalid escape stays as it is."""
    data = data.replace(b"+", b" ")
    if b"%" in data:
        data = _ESCAPE.sub(
            lambda m: bytes([int(m.group(1) or m.group(2), 16) & 0xFF]), data)
    return _fold_overlong(data)


_ENTITIES = {b"lt": b"<", b"gt": b">", b"amp": b"&", b"quot": b'"',
             b"apos": b"'", b"nbsp": b" ", b"sol": b"/", b"bsol": b"\\",
             b"colon": b":", b"semi": b";", b"equals": b"=", b"lpar": b"(",
             b"rpar": b")"}
_ENTITY_RX = re.compile(rb"&(#[xX][0-9a-fA-F]{1,7}|#[0-9]{1,8}|[A-Za-z]{1,8});")


def html_entity_decode(data: bytes) -> bytes:
    """&#NN; &#xHH; and the named entities above; the `;` is required."""
    def one(m):
        body = m.group(1)
        if body[:1] == b"#":
            code = int(body[2:], 16) if body[1:2] in b"xX" else int(body[1:])
            return bytes([code & 0xFF])
        return _ENTITIES.get(body.lower(), m.group(0))
    return _ENTITY_RX.sub(one, data)


def normalize_path(data: bytes) -> bytes:
    while b"//" in data:
        data = data.replace(b"//", b"/")
    data = data.replace(b"/./", b"/")
    kept: List[bytes] = []
    for seg in data.split(b"/"):
        if seg == b".." and kept and kept[-1] not in (b"", b".."):
            kept.pop()
        else:
            kept.append(seg)
    return b"/".join(kept)


def cmd_line(data: bytes) -> bytes:
    data = re.sub(rb"[\\'\"^]", b"", data).lower()
    data = re.sub(rb"[\s\x0b]+", b" ", data)
    data = re.sub(rb" ?([/(]) ?", rb"\1", data)
    return data.strip(b" \t\n\r\f\v")


def css_decode(data: bytes) -> bytes:
    return re.sub(rb"\\([0-9a-fA-F]{1,6})\s?",
                  lambda m: bytes([int(m.group(1), 16) & 0xFF]), data)


def replace_comments(data: bytes) -> bytes:
    data = re.sub(rb"(?s)/\*.*?\*/", b" ", data)
    cut = data.find(b"/*")
    return data if cut < 0 else data[:cut] + b" "


TRANSFORMS = {
    "lowercase": bytes.lower,
    "urlDecodeUni": url_decode_uni,
    "urlDecode": url_decode_uni,
    "htmlEntityDecode": html_entity_decode,
    "removeNulls": lambda d: d.replace(b"\x00", b""),
    "compressWhitespace": lambda d: re.sub(rb"[\s\x0b]+", b" ", d),
    "removeWhitespace": lambda d: re.sub(rb"[\s\x0b]+", b"", d),
    "replaceComments": replace_comments,
    "removeCommentsChar": lambda d: re.sub(rb"/\*|\*/|--|#", b"", d),
    "normalizePath": normalize_path,
    "normalizePathWin": normalize_path,
    "cmdLine": cmd_line,
    "cssDecode": css_decode,
}


def transform(data: bytes, names: Sequence[str]) -> bytes:
    for name in names:
        data = TRANSFORMS[name](data)
    return data


# ----------------------------------------- strict-grammar operators

_SQL_KEYWORDS = frozenset("""select union insert update delete drop create
 alter truncate replace merge exec execute declare from where having group
 order limit offset into values table database and or not like between in
 is null case when then else end cast convert waitfor delay""".split())
_SQL_FUNCTIONS = frozenset("""sleep benchmark pg_sleep load_file version user
 current_user session_user system_user database schema concat group_concat
 char chr ascii substring substr mid hex unhex extractvalue updatexml
 xp_cmdshell randomblob sqlite_version utl_inaddr dbms_pipe""".split())
_STATEMENTS = frozenset("""select insert update delete drop create alter exec
 execute declare truncate""".split())
_SQL_LEX = re.compile(rb"""(?sx)
      (?P<space>\s+)
    | (?P<comment>--[^\n]*|\#[^\n]*|/\*.*?(?:\*/|\Z))
    | (?P<string>'(?:[^'\\]|\\.|'')*'?|"(?:[^"\\]|\\.|"")*"?|`[^`]*`?)
    | (?P<hex>0x[0-9a-fA-F]+)
    | (?P<number>\d+(?:\.\d+)?)
    | (?P<word>[A-Za-z_][A-Za-z0-9_$]*)
    | (?P<op>\|\||&&|<=|>=|<>|!=|@@|[=<>+\-*/%(),;@!~^&|])
    """)
_COMPARE = (b"=", b"<", b">", b"<=", b">=", b"<>", b"!=", b"like")


def _sql_tokens(text: bytes) -> List[Tuple[str, bytes]]:
    """(kind, text) with kind in comment string hex number word fn op or
    a keyword's own name; unknown bytes are skipped; at most 512."""
    toks: List[Tuple[str, bytes]] = []
    at = 0
    while at < len(text) and len(toks) < 512:
        m = _SQL_LEX.match(text, at)
        if m is None:
            at += 1
            continue
        at = m.end()
        kind, lit = m.lastgroup, m.group()
        if kind == "space":
            continue
        if kind == "word":
            low = lit.lower().decode()
            if low in _SQL_KEYWORDS:
                kind = "kw:" + low
            elif low in _SQL_FUNCTIONS:
                kind = "fn"
        toks.append((kind, lit))
    return toks


def _value(tok) -> bool:
    return tok[0] in ("string", "number", "hex", "word", "fn")


def _sql_shapes(toks: List[Tuple[str, bytes]]) -> bool:
    n = len(toks)
    for i, (kind, lit) in enumerate(toks):
        if kind == "kw:union":
            # UNION [ALL|DISTINCT] SELECT, comments and '(' between
            j, modifier = i + 1, False
            while j < n:
                k, t = toks[j]
                if k == "comment" or (k == "op" and t == b"("):
                    j += 1
                elif (not modifier and k == "word"
                      and t.lower() in (b"all", b"distinct")):
                    modifier = True
                    j += 1
                else:
                    break
            if j < n and toks[j][0] == "kw:select":
                return True
        if kind == "kw:select":
            # SELECT list FROM ref: FROM within 32 tokens, and no run of
            # three bare words between SELECT and three tokens past FROM
            for j in range(i + 1, min(i + 33, n)):
                if toks[j][0] == "kw:from":
                    run = 0
                    prose = False
                    for k, _t in toks[i + 1:min(j + 4, n)]:
                        run = run + 1 if k == "word" else 0
                        prose = prose or run >= 3
                    if not prose:
                        return True
                    break
        if kind == "op" and lit == b";":
            if any(k[:3] == "kw:" and k[3:] in _STATEMENTS
                   for k, _t in toks[i + 1:i + 4]):
                return True
        if kind in ("kw:or", "kw:and") and i + 3 <= n:
            rest = toks[i + 1:]
            plain = [t for t in rest if t[0] != "comment"]
            if (len(plain) >= 3 and _value(plain[0])
                    and plain[1][1].lower() in _COMPARE and _value(plain[2])):
                return True
            if rest and _value(rest[0]):
                if len(rest) == 1:
                    return True
                if rest[1][0] == "comment" and (
                        len(rest) == 2 or rest[1][1][:2] == b"--"
                        or rest[1][1][:1] == b"#"):
                    return True
        if kind == "fn" and i + 1 < n and toks[i + 1][1] == b"(":
            return True
    literal = ("string", "number", "hex")
    return (n >= 3 and toks[0][0] in literal and toks[2][0] in literal
            and toks[1][1] in (b"=", b"<>", b"!="))


def detect_sqli(value: bytes) -> bool:
    value = value[:DETECT_WINDOW]
    if not value:
        return False
    for quote in (b"", b"'", b'"'):
        text = quote + value if quote and quote in value else value
        toks = _sql_tokens(text)
        if not toks:
            continue
        if quote and len(toks) >= 2 and toks[0][0] == "string" \
                and toks[-1][0] == "comment":
            return True
        if _sql_shapes(toks):
            return True
    return False


_ACTIVE_TAGS = frozenset(b"""script iframe embed object applet svg math base
 meta form video audio img input body style link marquee details
 template""".split())


def detect_xss(value: bytes) -> bool:
    low = value[:DETECT_WINDOW].lower()
    if not low:
        return False
    for m in re.finditer(rb"<\s*/?\s*([a-z][a-z0-9-]*)", low):
        if m.group(1) in _ACTIVE_TAGS:
            return True
    return bool(
        re.search(rb"(?s)\bon[a-z]{3,30}\s*=\s*[\"'`]?[^\s\"'`>]", low)
        or re.search(rb"(?:javascript|vbscript)\s*:", low)
        or re.search(rb"data\s*:[^,]{0,60};\s*base64", low)
        or (b"&#" in low and b"script" in low))


# -------------------------------------------------------------- rules

class Target:
    def __init__(self, token: str):
        self.count = token.startswith("&")
        base, sep, sel = token.lstrip("&").partition(":")
        self.base = base.strip().upper()
        self.selector = sel.strip().lower().encode() if sep else None


class Rule:
    def __init__(self):
        self.rule_id = 0
        self.targets: List[Target] = []
        self.streams: Optional[List[str]] = None    # signature-pack rule
        self.op = "rx"
        self.arg = ""
        self.negate = False
        self.transforms: List[str] = []
        self.action = "block"
        self.severity = "WARNING"
        self.paranoia = 1
        self.chain: List["Rule"] = []
        self.test = None

    def prepare(self) -> None:
        for name in self.transforms:
            if name not in TRANSFORMS:
                raise NotModelled("transform t:%s (rule %d)" % (name, self.rule_id))
        self.chain_key = tuple(self.transforms)
        self.test = _operator(self)
        for link in self.chain:
            link.prepare()


def _atoi(text: bytes) -> int:
    m = re.match(rb"\s*([+-]?\d+)", text)
    return int(m.group(1)) if m else 0


def _byte_set(arg: str) -> bytes:
    allowed = set()
    for part in arg.split(","):
        lo, _, hi = part.strip().partition("-")
        allowed.update(range(int(lo), int(hi or lo) + 1))
    return bytes(sorted(allowed))


def _operator(rule: Rule):
    """The rule's operator as value -> True / False / None (cannot say)."""
    op, arg = rule.op, rule.arg
    raw = arg.encode("utf-8", "surrogateescape")
    if op == "rx":
        rx = re.compile(raw)
        return lambda v: rx.search(v) is not None
    if op == "pm":
        words = [w.lower().encode() for w in arg.split("\n") if w]
        return lambda v: any(w in v.lower() for w in words)
    if op == "streq":
        return lambda v: v == raw
    if op == "within":
        return lambda v: v in raw
    if op in ("eq", "gt"):
        ref = _atoi(raw)
        return (lambda v: _atoi(v) == ref) if op == "eq" \
            else (lambda v: _atoi(v) > ref)
    if op == "validateByteRange":
        allowed = _byte_set(arg)
        return lambda v: bool(v.translate(None, allowed))
    if op == "validateUrlEncoding":
        return lambda v: re.search(rb"%(?![0-9a-fA-F]{2})", v) is not None
    if op == "validateUtf8Encoding":
        def bad_utf8(v):
            try:
                v.decode("utf-8")
                return False
            except UnicodeDecodeError:
                return True
        return bad_utf8
    if op == "detectSQLi":
        return detect_sqli
    if op == "detectXSS":
        return detect_xss
    if op == "ipMatch":
        nets = [ipaddress.ip_network(p.strip(), strict=False)
                for p in arg.split(",") if p.strip()]

        def in_nets(v):
            try:
                ip = ipaddress.ip_address(v.decode("ascii").strip())
            except ValueError:
                return None
            return any(ip in net for net in nets)
        return in_nets
    raise NotModelled("operator @%s (rule %d)" % (op, rule.rule_id))


def _logical_lines(text: str) -> List[str]:
    out, cur = [], ""
    for raw in text.splitlines():
        line = raw.rstrip()
        if not cur and (not line.strip() or line.lstrip().startswith("#")):
            continue
        if line.endswith("\\"):
            cur += line[:-1] + " "
            continue
        out.append((cur + line).strip())
        cur = ""
    if cur.strip():
        out.append(cur.strip())
    return out


def _words(line: str) -> List[str]:
    """Directive words: blank-separated; "..." may hold blanks, and in it
    a backslash escapes only a double quote or a backslash."""
    words, i, n = [], 0, len(line)
    while i < n:
        if line[i].isspace():
            i += 1
            continue
        buf = []
        if line[i] == '"':
            i += 1
            while i < n and line[i] != '"':
                if line[i] == "\\" and i + 1 < n and line[i + 1] in '"\\':
                    i += 1
                buf.append(line[i])
                i += 1
            i += 1
        else:
            while i < n and not line[i].isspace():
                buf.append(line[i])
                i += 1
        words.append("".join(buf))
    return words


def _actions(text: str) -> List[Tuple[str, str]]:
    """`a,b:c,d:'e,f'` -> [(a, ''), (b, c), (d, 'e,f')]."""
    items, buf, quoted = [], [], False
    for ch in text:
        if ch == "'":
            quoted = not quoted
        elif ch == "," and not quoted:
            items.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
    items.append("".join(buf))
    out = []
    for item in items:
        key, _, val = item.strip().partition(":")
        if key.strip():
            out.append((key.strip(), val.strip()))
    return out


#: what a SecRule may carry here; most say nothing about the verdict
_KNOWN_ACTIONS = {"id", "phase", "msg", "tag", "severity", "t", "chain",
                  "block", "deny", "pass", "nolog", "log", "auditlog",
                  "noauditlog", "capture", "rev", "ver", "maturity",
                  "accuracy", "logdata", "status", "setvar"}


def _list_file(conf_dir: Path, name: str) -> List[str]:
    return [w.strip() for w in (conf_dir / name).read_text().splitlines()
            if w.strip() and not w.startswith("#")]


def _sec_rule(words: List[str], conf_dir: Path) -> Tuple[Rule, bool]:
    if len(words) < 3:
        raise NotModelled("short SecRule: %r" % (words,))
    rule = Rule()
    for tok in words[1].split("|"):
        tok = tok.strip()
        if tok.startswith("!"):
            raise NotModelled("target exclusion %s" % tok)
        if tok:
            rule.targets.append(Target(tok))
    op = words[2]
    if op.startswith("!"):
        rule.negate, op = True, op[1:]
    if op.startswith("@"):
        name, _, arg = op[1:].partition(" ")
        rule.op, rule.arg = name, arg.strip()
    else:
        rule.op, rule.arg = "rx", op
    if rule.op == "pmFromFile":
        rule.op, rule.arg = "pm", "\n".join(_list_file(conf_dir, rule.arg))
    elif rule.op == "ipMatchFromFile":
        rule.op, rule.arg = "ipMatch", ",".join(_list_file(conf_dir, rule.arg))
    has_chain = False
    for key, val in _actions(words[3] if len(words) > 3 else ""):
        if key not in _KNOWN_ACTIONS:
            raise NotModelled("action %s (rule %d)" % (key, rule.rule_id))
        if key == "id":
            rule.rule_id = int(val)
        elif key == "t":
            if val == "none":
                rule.transforms = []
            else:
                rule.transforms.append(val)
        elif key in ("block", "deny", "pass"):
            rule.action = key
        elif key == "severity":
            rule.severity = val
        elif key == "tag":
            m = re.search(r"paranoia-level/(\d)", val)
            if m:
                rule.paranoia = int(m.group(1))
        elif key == "chain":
            has_chain = True
        elif key == "setvar":
            raise NotModelled("setvar on a SecRule (rule %d)" % rule.rule_id)
    return rule, has_chain


class Deployment:
    """The rules as served: SecLang tree (+ signature packs), the TX
    settings of its SecActions, and the served paranoia level."""

    def __init__(self, rules_dir: Path, sigpack: Optional[Path] = None,
                 paranoia: Optional[int] = None):
        self.rules: List[Rule] = []
        self.tx: Dict[str, str] = {}
        for conf in sorted(Path(rules_dir).glob("*.conf")):
            self._load_conf(conf)
        if sigpack is not None:
            self._load_sigpack(Path(sigpack))
        self.threshold = int(self.tx.get("inbound_anomaly_score_threshold", 5))
        self.paranoia = paranoia if paranoia is not None else int(
            self.tx.get("detection_paranoia_level", 2))
        for rule in self.rules:
            rule.prepare()
        self.served = [r for r in self.rules if r.paranoia <= self.paranoia]

    def _load_conf(self, conf: Path) -> None:
        open_chain: Optional[Rule] = None
        for line in _logical_lines(conf.read_text()):
            words = _words(line)
            if words[0] == "SecAction":
                for key, val in _actions(words[1]):
                    if key == "setvar":
                        name, _, value = val.partition("=")
                        if not name.lower().startswith("tx.") or "%{" in value \
                                or value[:1] in "+-":
                            raise NotModelled("setvar %s" % val)
                        self.tx[name[3:].lower()] = value
                    elif key in ("skipAfter", "ctl"):
                        raise NotModelled("SecAction %s" % key)
            elif words[0] == "SecRule":
                rule, more = _sec_rule(words, conf.parent)
                if open_chain is None:
                    self.rules.append(rule)
                    open_chain = rule if more else None
                else:
                    open_chain.chain.append(rule)
                    if not more:
                        open_chain = None
            else:
                raise NotModelled("directive %s in %s" % (words[0], conf.name))

    def _load_sigpack(self, path: Path) -> None:
        doc = json.loads(path.read_text())
        for pack in doc["packs"]:
            rid = int(pack["base_id"])
            for template in pack["templates"]:
                for word in pack["keywords"]:
                    rule = Rule()
                    rule.rule_id = rid
                    rule.streams = list(pack["streams"])
                    rule.arg = template.replace("{w}", word)
                    rule.transforms = list(doc["transforms"])
                    rule.action = doc["action"]
                    rule.severity = pack["severity"]
                    rule.paranoia = int(doc["paranoia"])
                    self.rules.append(rule)
                    rid += 1


# ---------------------------------------------------------- variables

def _split_pairs(raw: bytes) -> List[Tuple[bytes, bytes]]:
    """k=v&k2=v2, split first and url-decoded after; a name-less pair is
    dropped, a value-less one has the empty value."""
    out = []
    for part in raw.split(b"&"):
        if not part:
            continue
        name, _, value = part.partition(b"=")
        name = url_decode_uni(name).strip()
        if name:
            out.append((name, url_decode_uni(value)))
    return out


def _json_scalar(o) -> bytes:
    if isinstance(o, str):
        return o.encode("utf-8", "surrogateescape")
    if isinstance(o, bool):
        return b"true" if o else b"false"
    if o is None:
        return b""
    return str(o).encode()


def _json_args(doc, name: bytes, out: list) -> None:
    if isinstance(doc, dict):
        for key, val in doc.items():
            _json_args(val, name + b"." + str(key).encode(
                "utf-8", "surrogateescape"), out)
    elif isinstance(doc, list):
        for val in doc:
            _json_args(val, name, out)
    else:
        out.append((name, _json_scalar(doc)))


def _json_strings(doc, out: list) -> None:
    """Keys and string values, depth first, in the document's order."""
    if isinstance(doc, dict):
        for key, val in doc.items():
            if isinstance(key, str) and key:
                out.append(key)
            _json_strings(val, out)
    elif isinstance(doc, list):
        for val in doc:
            _json_strings(val, out)
    elif isinstance(doc, str) and doc:
        out.append(doc)


_B64_SHAPE = re.compile(rb"\A[A-Za-z0-9+/\-_\s]+={0,2}\s*\Z")


class Variables:
    """One request's variables, built on demand."""

    def __init__(self, req: HttpRequest, value_head: Optional[int] = None):
        self.req = req
        #: a control's weakening: only this many bytes of a value are seen
        self.value_head = value_head
        path, mark, query = req.uri.partition(b"?")
        self.scalars = {
            "REQUEST_URI": req.uri, "REQUEST_URI_RAW": req.uri,
            "REQUEST_FILENAME": path,
            "REQUEST_BASENAME": path.rsplit(b"/", 1)[-1],
            "QUERY_STRING": query if mark else b"",
            "REQUEST_METHOD": req.method.encode(),
        }
        self.headers = [(n.strip(), v.strip()) for n, v in req.headers]
        self.ctype = b""
        for name, value in self.headers:
            low = name.lower()
            if low == b"content-type" and not self.ctype:
                self.ctype = value.lower()
            elif low == b"content-encoding":
                raise NotModelled("compressed body")
        self._memo: Dict[str, object] = {}
        #: (transform names, value) -> transformed value: rules share chains
        self.transformed: Dict[tuple, bytes] = {}

    def _json(self):
        if "json" not in self._memo:
            try:
                self._memo["json"] = json.loads(
                    self.req.body.decode("utf-8", "surrogateescape"))
            except ValueError:
                raise NotModelled("a JSON body that does not parse")
        return self._memo["json"]

    def unpacked_body(self) -> bytes:
        if "body" in self._memo:
            return self._memo["body"]
        body = self.req.body
        if body[:2] == b"\x1f\x8b":
            raise NotModelled("compressed body")
        if b"xml" in self.ctype or body.lstrip()[:5] == b"<?xml" \
                or b"grpc" in self.ctype or b"proto" in self.ctype:
            raise NotModelled("xml or protobuf body")
        parts = [body]
        if body and (b"json" in self.ctype or body.lstrip()[:1] in (b"{", b"[")):
            if b"json" in self.ctype:
                doc = self._json()
            else:
                try:
                    doc = json.loads(body.decode("utf-8", "surrogateescape"))
                except ValueError:
                    doc = None
            strings: List[str] = []
            _json_strings(doc, strings)
            if strings:
                joined = SEP.join(s.encode("utf-8", "surrogateescape")
                                  for s in strings)
                if joined != body:
                    parts.append(joined)
        token = body.strip()
        if len(token) >= 16 and _B64_SHAPE.match(token):
            compact = re.sub(rb"\s+", b"", token).replace(b"-", b"+") \
                .replace(b"_", b"/")
            try:
                plain = base64.b64decode(compact + b"=" * (-len(compact) % 4),
                                         validate=True)
            except (binascii.Error, ValueError):
                plain = b""
            if plain:
                parts.append(plain)
        self._memo["body"] = SEP.join(parts)
        return self._memo["body"]

    def collection(self, kind: str) -> List[Tuple[bytes, bytes]]:
        if kind in self._memo:
            return self._memo[kind]
        if kind == "headers":
            out = self.headers
        elif kind == "cookies":
            out = []
            for name, value in self.headers:
                if name.lower() == b"cookie":
                    for part in value.split(b";"):
                        k, _, v = part.partition(b"=")
                        if k.strip():
                            out.append((k.strip(), v.strip()))
        elif kind == "get":
            out = _split_pairs(self.scalars["QUERY_STRING"])
        elif kind == "post":
            body = self.req.body
            out = []
            if not body:
                pass
            elif b"multipart/form-data" in self.ctype:
                raise NotModelled("multipart body")
            elif b"json" in self.ctype:
                _json_args(self._json(), b"json", out)
                if len(out) > 512:
                    raise NotModelled("a JSON body of over 512 values")
            elif b"application/x-www-form-urlencoded" in self.ctype:
                out = _split_pairs(body)
            elif not self.ctype:
                raise NotModelled("a body with no content type")
        elif kind == "args":
            out = self.collection("get") + self.collection("post")
        else:
            raise NotModelled("collection %s" % kind)
        self._memo[kind] = out
        return out

    COLLECTIONS = {
        "REQUEST_HEADERS": ("headers", 1), "REQUEST_HEADERS_NAMES": ("headers", 0),
        "REQUEST_COOKIES": ("cookies", 1), "REQUEST_COOKIES_NAMES": ("cookies", 0),
        "ARGS": ("args", 1), "ARGS_NAMES": ("args", 0),
        "ARGS_GET": ("get", 1), "ARGS_GET_NAMES": ("get", 0),
        "ARGS_POST": ("post", 1), "ARGS_POST_NAMES": ("post", 0),
    }
    #: variables of a request that this wire never carries, of a response,
    #: or of an upload: they have no value here, so no operator runs
    ABSENT = {"REQUEST_PROTOCOL", "REMOTE_ADDR", "RESPONSE_BODY",
              "RESPONSE_STATUS", "RESPONSE_HEADERS", "RESPONSE_HEADERS_NAMES",
              "FILES", "FILES_NAMES"}

    def values(self, target: Target) -> List[Tuple[bytes, bool]]:
        """[(value, is_count)] of one target token."""
        base = target.base
        if base in self.COLLECTIONS:
            kind, part = self.COLLECTIONS[base]
            items = self.collection(kind)
            if target.selector is not None:
                items = [it for it in items if it[0].lower() == target.selector]
            if target.count:
                return [(str(len(items)).encode(), True)]
            return [(it[part], False) for it in items]
        if base in self.ABSENT:
            if base in ("FILES", "FILES_NAMES") and \
                    b"multipart/form-data" in self.ctype:
                raise NotModelled("multipart body")
            return []
        if base == "REQUEST_BODY":
            value = self.unpacked_body()
        elif base in self.scalars:
            value = self.scalars[base]
        else:
            raise NotModelled("variable %s" % base)
        if target.count:
            return [(b"1" if value else b"0", True)]
        return [(value, False)] if value else []

    def stream(self, name: str) -> bytes:
        """A whole stream, as a signature-pack rule looks at it."""
        if name == "uri":
            return self.req.uri
        if name == "args":
            return url_decode_uni(self.scalars["QUERY_STRING"])
        if name == "body":
            return self.unpacked_body()
        if name == "headers":
            return SEP.join(n + b": " + v for n, v in self.req.headers)
        raise NotModelled("stream %s" % name)


# --------------------------------------------------------- evaluation

def _link(rule: Rule, var: Variables,
          before: Optional[List[bytes]]) -> Optional[List[bytes]]:
    """One rule or chain link.  None = no match; else the values (after
    the link's transforms) that matched, in target order."""
    if rule.streams is not None:
        cands = [(var.stream(s), False) for s in rule.streams]
        cands = [c for c in cands if c[0]]
    else:
        cands = []
        for target in rule.targets:
            if target.base in ("MATCHED_VAR", "MATCHED_VARS"):
                if target.count or target.selector is not None:
                    raise NotModelled("&MATCHED_VAR")
                prior = before or []
                cands += [(v, False) for v in
                          (prior[-1:] if target.base == "MATCHED_VAR" else prior)]
            else:
                cands += var.values(target)
    matched: List[bytes] = []
    hit = False
    for value, is_count in cands:
        if not is_count and var.value_head is not None:
            value = value[:var.value_head]
        if not is_count and rule.transforms:
            key = (rule.chain_key, value)
            if key not in var.transformed:
                var.transformed[key] = transform(value, rule.transforms)
            value = var.transformed[key]
        result = rule.test(value)
        if result is None or result == rule.negate:
            continue
        hit = True
        if not is_count:
            matched.append(value)
    return matched if hit else None


def rule_matches(rule: Rule, var: Variables) -> bool:
    state = _link(rule, var, None)
    for link in rule.chain:
        if state is None:
            return False
        state = _link(link, var, state)
    return state is not None


def verdict(dep: Deployment, req: HttpRequest,
            value_head: Optional[int] = None) -> Tuple[bool, bool, List[int]]:
    """(attack, blocked, ids of the rules that matched)."""
    var = Variables(req, value_head)
    fired = [r for r in dep.served if rule_matches(r, var)]
    score = sum(SEVERITY_SCORE.get(r.severity.upper(), 3) for r in fired)
    attack = bool(fired) and score >= dep.threshold
    blocked = attack or any(r.action == "deny" for r in fired)
    return attack, blocked, [r.rule_id for r in fired]
