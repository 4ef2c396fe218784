"""The ten readers of the program's sub-spans and counters (ISSUE 27), on
a recorded pair of scrapes.

`data/substage_scrape_{before,after}.txt` are two `/metrics` scrapes of a
CPU server around 192 requests (PR 27), cut to the series the readers
read.  They are arithmetic fixtures: nothing here is a device number.
Each expectation is reckoned by hand from the two files.
"""

import importlib.util
from pathlib import Path

import pytest

from harness import scrape

BENCH = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"
SECONDS = 0.7          # the recorded window's length


def reader(name):
    path = BENCH / "layer_metrics" / (name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def ctx():
    before = scrape.parse_metrics(
        (DATA / "substage_scrape_before.txt").read_text())
    after = scrape.parse_metrics(
        (DATA / "substage_scrape_after.txt").read_text())
    return {"window": scrape.Window(before, after), "seconds": SECONDS}


#: metric -> (the window's difference of the sum, of the count)
STAGE_MEANS = {
    "dispatch.pack_ms": (2260 - 457, 12),
    "dispatch.launch_ms": (1302603 - 677844, 12),
    "dispatch.wait_ms": (3374 - 228, 12),
    "confirm.walk_ms": (4814 - 707, 12),
    "confirm.fold_ms": (2512 - 365, 12),
    "dispatch.handoff_ms": (8760 - 779, 12),
    "sidecar.reply_lag_ms": (242944 - 61436, 192),
}


@pytest.mark.parametrize("name", sorted(STAGE_MEANS))
def test_stage_mean_readers(ctx, name):
    us, n = STAGE_MEANS[name]
    assert reader(name)(ctx) == pytest.approx(us / n / 1e3)


def test_launches_per_dispatch(ctx):
    assert reader("dispatch.launches_per_dispatch")(ctx) == pytest.approx(
        (91 - 7) / 12)


def test_loop_busy_share(ctx):
    idle_s = (678497 - 608842) / 1e6
    assert reader("batcher.loop_busy_share")(ctx) == pytest.approx(
        100.0 * (1.0 - idle_s / SECONDS))


def test_gc_pause_share(ctx):
    pauses_us = (14443 - 11249) + (11169 - 9188) + (0 - 0)
    assert reader("batcher.gc_pause_share")(ctx) == pytest.approx(
        100.0 * pauses_us / 1e6 / SECONDS)


NEW = sorted(STAGE_MEANS) + ["dispatch.launches_per_dispatch",
                             "batcher.loop_busy_share",
                             "batcher.gc_pause_share"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_reads_nothing(name):
    """The parent of PR 27 has none of these spans and counters: a reader
    then returns None (the line leaves the metric out) and never raises."""
    old = scrape.parse_metrics(
        'ipt_stage_us_sum{stage="scan"} 100\n'
        'ipt_stage_us_count{stage="scan"} 4\n'
        'ipt_stage_us_sum{stage="batch"} 900\n'
        'ipt_stage_us_count{stage="batch"} 4\n'
        'ipt_requests_total 64\n')
    ctx = {"window": scrape.Window({}, old), "seconds": 2.0}
    assert reader(name)(ctx) is None


def test_the_sub_spans_lie_inside_their_stages(ctx):
    """What PERF.md states of the residue, on the recorded pair: pack +
    launch + wait is within the scan stage, walk + fold within confirm."""
    w = ctx["window"]
    parts = sum(reader(n)(ctx) for n in
                ("dispatch.pack_ms", "dispatch.launch_ms", "dispatch.wait_ms"))
    assert 0.95 * w.stage_mean_ms("scan") <= parts <= w.stage_mean_ms("scan")
    parts = reader("confirm.walk_ms")(ctx) + reader("confirm.fold_ms")(ctx)
    assert parts <= w.stage_mean_ms("confirm")


def test_benchmark_json_lists_the_ten_beside_the_thirteen():
    import json

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == 23 and names[13:] == [
        "dispatch.pack_ms", "dispatch.launch_ms", "dispatch.wait_ms",
        "dispatch.launches_per_dispatch", "confirm.walk_ms",
        "confirm.fold_ms", "dispatch.handoff_ms", "batcher.loop_busy_share",
        "sidecar.reply_lag_ms", "batcher.gc_pause_share"]
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"][13:]:
        assert m["workloads"] == cells
        assert (BENCH / "layer_metrics" / (m["name"] + ".py")).is_file()
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
