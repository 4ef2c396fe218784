"""Packaging renderer (Helm-chart analog), trace ring, and the
postanalytics consolidator CLI — golden-file style like the reference's
template_test.go† (SURVEY.md §4)."""

import json
import time
from pathlib import Path

from ingress_plus_tpu.control.deploy import (
    DeployValues,
    render_all,
    write_static,
)

REPO = Path(__file__).resolve().parent.parent


def test_render_contains_architecture():
    v = DeployValues(chips_per_host=2, balance="ewma", deadline_ms=30)
    out = render_all(v)
    dep = out["deployment.yaml"]
    # one serve loop per chip, each with its own socket + chip binding
    assert dep.count("name: serve-") == 2
    assert "/run/ipt/serve-0.sock" in dep and "/run/ipt/serve-1.sock" in dep
    assert "google.com/tpu: 1" in dep
    # sidecar balances across both and owns the fail-open deadline
    assert "- /run/ipt/serve-0.sock,/run/ipt/serve-1.sock" in dep
    assert "- ewma" in dep
    assert '- "30"' in dep
    # liveness probes wired to the serve loops' /healthz
    assert dep.count("path: /healthz") == 2
    # postanalytics consolidator shares the pod's spool emptyDir (a
    # separate Deployment's emptyDir would always be empty)
    assert "ingress_plus_tpu.post.export" in dep
    assert dep.count("name: ipt-spool, mountPath") >= 3
    cm = out["configmap.yaml"]
    assert 'detection-backend: "tpu"' in cm
    assert 'fail-open: "true"' in cm
    assert "attacks" not in out["service.yaml"]  # no hot-path port leaks


def test_render_fleet_topology():
    """Fleet tier (ISSUE 19): front + N serve replicas + aggregator +
    retune daemon in one pod, readiness probes on every layer."""
    v = DeployValues(fleet_nodes=4, front_http_port=9931,
                     fleet_http_port=9912)
    fleet = render_all(v)["fleet.yaml"]
    # N replicas, each on its own UDS + HTTP plane with its own probes
    assert fleet.count("name: serve-") == 4
    for i in range(4):
        assert "/run/ipt/fleet-%d.sock" % i in fleet
    assert fleet.count("path: /readyz") == 4 + 1  # replicas + front
    assert fleet.count("path: /healthz") == 4
    # the front knows every backend by socket AND HTTP plane
    assert "- --front" in fleet
    assert fleet.count("- --backend") == 4
    assert "n0=/run/ipt/fleet-0.sock@127.0.0.1:9941" in fleet
    # aggregator scrapes all replicas; daemon closes the loop on the
    # aggregator's /fleet/* surfaces and shares the fleet LKG volume
    assert "ingress_plus_tpu.control.fleetobs" in fleet
    assert "ingress_plus_tpu.control.retuned" in fleet
    assert fleet.count("- --node") == 8  # aggregator + daemon
    assert "path: /fleet/healthz" in fleet
    assert "- 127.0.0.1:9912" in fleet  # daemon -> aggregator, pod-local
    assert fleet.count("name: ipt-fleet-lkg") >= 6  # volume + mounts
    # front + aggregator are the only ports the Service exposes; the
    # replicas' HTTP planes stay pod-local (scraped by the aggregator)
    assert "port: 9931" in fleet and "port: 9912" in fleet
    # fleet tier is opt-out: 0 nodes renders no fleet manifest at all
    assert "fleet.yaml" not in render_all(DeployValues(fleet_nodes=0))


def test_static_manifests_in_sync(tmp_path):
    """deploy/static must equal a fresh default render (the reference
    regenerates deploy/static from the chart the same way)."""
    fresh = tmp_path / "static"
    write_static(fresh)
    committed = REPO / "deploy" / "static"
    fresh_names = sorted(p.name for p in fresh.iterdir())
    assert sorted(p.name for p in committed.iterdir()) == fresh_names, \
        "deploy/static file set is stale"
    for f in fresh.iterdir():
        assert (committed / f.name).read_text() == f.read_text(), \
            "deploy/static/%s is stale — run python -m " \
            "ingress_plus_tpu.control.deploy" % f.name


def test_values_yaml_drives_render():
    """The one-values-file packaging contract (VERDICT round-2 item 8):
    deploy/values.yaml parses into DeployValues, every key is honored,
    and a typo'd key fails loudly."""
    import pytest

    text = (REPO / "deploy" / "values.yaml").read_text()
    v = DeployValues.from_yaml(text)
    assert v.namespace == "ingress-plus-tpu" and v.chips_per_host == 4
    # committed values == defaults, so the committed static render is
    # exactly what the values file produces
    assert render_all(v) == render_all(DeployValues())

    custom = DeployValues.from_yaml(
        "replicas: 5\nbalance: chash\nfail-open: false\n"
        "deadline-ms: 75\ntenants:\n  1: [attack-sqli, attack-xss]\n")
    assert custom.replicas == 5 and custom.balance == "chash"
    assert custom.fail_open is False and custom.deadline_ms == 75
    assert custom.tenants == {1: ["attack-sqli", "attack-xss"]}
    dep = render_all(custom)["deployment.yaml"]
    assert "replicas: 5" in dep and "chash" in dep

    with pytest.raises(ValueError, match="unknown key"):
        DeployValues.from_yaml("replcias: 5\n")


def test_trace_ring_bounds_and_slowest():
    from ingress_plus_tpu.utils.trace import BatchTrace, TraceRing

    ring = TraceRing(capacity=8)
    for i in range(20):
        ring.record(BatchTrace(
            ts=float(i), n_requests=1, n_stream_items=0, queue_delay_us=5,
            batch_us=1000 + i, engine_us=800, confirm_us=50,
            request_ids=["r%d" % i]))
    snap = ring.snapshot()
    assert len(snap) == 8                      # bounded
    assert snap[-1]["request_ids"] == ["r19"]  # newest kept
    slow = ring.slowest(3)
    assert [t["batch_us"] for t in slow] == [1019, 1018, 1017]


def test_batcher_records_traces():
    from ingress_plus_tpu.compiler.ruleset import compile_ruleset
    from ingress_plus_tpu.compiler.seclang import parse_seclang
    from ingress_plus_tpu.models.pipeline import DetectionPipeline
    from ingress_plus_tpu.serve.batcher import Batcher
    from ingress_plus_tpu.serve.normalize import Request

    rules = """
SecRule ARGS "@rx (?i)union\\s+select" "id:942100,phase:2,block,severity:CRITICAL,tag:'attack-sqli'"
"""
    b = Batcher(DetectionPipeline(compile_ruleset(parse_seclang(rules))),
                max_delay_s=0.001)
    try:
        fut = b.submit(Request(uri="/?q=1%20union%20select%20x",
                               request_id="t-1"))
        assert fut.result(timeout=60).attack
        # the verdict resolves first and the cycle's trace is cut after
        # it, on the dispatch thread: give that thread its turn
        deadline = time.monotonic() + 10
        while not (traces := b.traces.snapshot()) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert traces and traces[-1]["n_requests"] == 1
        assert traces[-1]["request_ids"] == ["t-1"]
        assert traces[-1]["batch_us"] > 0
    finally:
        b.close()


def test_consolidator_cli(tmp_path):
    from ingress_plus_tpu.post.export import consolidate_once

    spool = tmp_path / "spool"
    spool.mkdir()
    records = [{"first_ts": 1.0, "classes": ["sqli"], "count": 3},
               {"first_ts": 2.0, "classes": ["xss"], "count": 1}]
    with (spool / "attacks.jsonl").open("w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    assert consolidate_once(spool) == 2
    assert not (spool / "attacks.jsonl").exists()          # claimed
    merged = (spool / "consolidated" / "attacks.jsonl").read_text()
    assert len(merged.splitlines()) == 2
    # idempotent on empty spool
    assert consolidate_once(spool) == 0
    # unreachable collector keeps the claim for retry (at-least-once)
    with (spool / "attacks.jsonl").open("w") as f:
        f.write(json.dumps(records[0]) + "\n")
    assert consolidate_once(spool, url="http://127.0.0.1:1/x") == 0
    assert list(spool.glob("attacks.*.sending"))
    assert consolidate_once(spool) == 1                    # retried, kept


def test_consolidator_salvages_torn_lines_and_multi_writer(tmp_path):
    """A torn line from a concurrent partial append must not discard the
    batch's valid records; per-pid spool files all get claimed."""
    from ingress_plus_tpu.post.export import consolidate_once

    spool = tmp_path / "spool"
    spool.mkdir()
    good = {"first_ts": 1.0, "classes": ["sqli"], "count": 2}
    with (spool / "attacks.101.jsonl").open("w") as f:
        f.write(json.dumps(good) + "\n")
        f.write('{"first_ts": 2.0, "classes": ["x')   # torn mid-append
    with (spool / "attacks.202.jsonl").open("w") as f:
        f.write(json.dumps(good) + "\n")
    assert consolidate_once(spool) == 2                    # both good lines
    assert not list(spool.glob("attacks*.jsonl"))          # all claimed
    assert not list(spool.glob("*.sending"))               # all consumed
    merged = (spool / "consolidated" / "attacks.jsonl").read_text()
    assert len(merged.splitlines()) == 2


def test_consolidator_requeues_bytes_appended_after_read(tmp_path,
                                                         monkeypatch):
    """Round-2 advisor: the claim-rename can land mid-append; a record
    the writer completes AFTER the consolidator's read must be requeued
    as a fresh .sending, not die with the unlink (at-least-once)."""
    import ingress_plus_tpu.post.export as export_mod
    from ingress_plus_tpu.post.export import consolidate_once

    spool = tmp_path / "spool"
    spool.mkdir()
    first = {"first_ts": 1.0, "classes": ["sqli"], "count": 2}
    late = {"first_ts": 9.0, "classes": ["xss"], "count": 1}
    live = spool / "attacks.303.jsonl"
    live.write_text(json.dumps(first) + "\n")

    # simulate the racing writer: its buffered line lands right after
    # the consolidator's read_bytes (hook the first stat via monkeypatch
    # of Path.stat is fragile; appending before consolidate and hooking
    # read is simplest: append after the read by patching read_bytes)
    real_read_bytes = export_mod.Path.read_bytes

    def read_then_append(self):
        data = real_read_bytes(self)
        if self.name.endswith(".sending") and "tail" not in self.name:
            with self.open("a") as fh:      # the writer's late flush
                fh.write(json.dumps(late) + "\n")
        return data

    monkeypatch.setattr(export_mod.Path, "read_bytes", read_then_append)
    assert consolidate_once(spool) == 1           # first record delivered
    monkeypatch.setattr(export_mod.Path, "read_bytes", real_read_bytes)

    # the late record was requeued, not lost
    tails = list(spool.glob("attacks.*_tail.sending"))
    assert len(tails) == 1
    assert consolidate_once(spool) == 1           # …and delivers next cycle
    merged = (spool / "consolidated" / "attacks.jsonl").read_text()
    got = [json.loads(l) for l in merged.splitlines()]
    assert first in got and late in got
