"""The six readers of the interpreter lock's measures (the off-CPU time of
a stage span, the side lane's parts, the Python threads' CPU), on
synthetic scrapes: the window's differences are set by hand below.  A
scrape without the series stands for a program that does not render them
(the parent of the change that added them): every reader reads nothing
there.  Arithmetic fixtures: nothing here is a device number."""

import importlib.util
from pathlib import Path

import pytest

from harness import scrape

BENCH = Path(__file__).resolve().parent.parent
SECONDS = 20.0

NEW = ("dispatch.launch_offcpu_ms", "host.python_threads_cpu_cores",
       "sidelane.fetch_ms", "sidelane.launch_offcpu_ms",
       "sidelane.lock_wait_ms", "sidelane.walk_ms")


def reader(name):
    path = BENCH / "layer_metrics" / (name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


#: stage -> (sum µs, count, CPU µs) before and after the window
STAGES = {
    "scan_launch": ((1_000_000, 400, 300_000), (8_600_000, 2_400, 2_100_000)),
    "side_scan": ((720_000, 10, 0), (18_720_000, 260, 0)),
    "side_launch": ((50_000, 10, 10_000), (1_050_000, 260, 160_000)),
    "side_prefilter": ((20_000, 10, 5_000), (520_000, 260, 105_000)),
    "side_fetch": ((600_000, 10, 1_000), (15_600_000, 260, 21_000)),
    "side_lock_wait": ((1_000, 10, 900), (26_000, 260, 20_900)),
    "side_walk": ((500_000, 10, 0), (13_000_000, 260, 0)),
}
#: thread root -> CPU seconds before and after
THREADS = {"dispatch": (30.0, 41.0), "lane_worker": (12.0, 18.0),
           "oversized": (4.0, 5.5), "MainThread": (9.0, 10.0)}


def text(i: int, stages=True, cpu=True, threads=True) -> str:
    lines = ["ipt_requests_total %d" % (100 * (i + 1))]
    for stage, sides in STAGES.items():
        us, n, cpu_us = sides[i]
        if stages:
            lines += ['ipt_stage_us_sum{stage="%s"} %d' % (stage, us),
                      'ipt_stage_us_count{stage="%s"} %d' % (stage, n)]
        if cpu and stage not in ("side_walk",):
            lines.append('ipt_stage_cpu_us_total{stage="%s"} %d'
                         % (stage, cpu_us))
    if threads:
        lines += ['ipt_thread_cpu_seconds_total{thread="%s"} %.6f' % (t, s[i])
                  for t, s in THREADS.items()]
    return "\n".join(lines) + "\n"


def ctx_of(**kw):
    return {"window": scrape.Window(scrape.parse_metrics(text(0, **kw)),
                                    scrape.parse_metrics(text(1, **kw))),
            "seconds": SECONDS}


def test_launch_off_cpu_is_the_wall_less_the_cpu_per_dispatch():
    off_us = (8_600_000 - 1_000_000) - (2_100_000 - 300_000)
    assert reader("dispatch.launch_offcpu_ms")(ctx_of()) == pytest.approx(
        off_us / 2_000 / 1e3)


def test_python_threads_cpu_cores_sums_every_root_over_the_window():
    cores = (11.0 + 6.0 + 1.5 + 1.0) / SECONDS
    assert reader("host.python_threads_cpu_cores")(ctx_of()) == \
        pytest.approx(cores)


@pytest.mark.parametrize("name,stage", [("sidelane.fetch_ms", "side_fetch"),
                                        ("sidelane.lock_wait_ms",
                                         "side_lock_wait"),
                                        ("sidelane.walk_ms", "side_walk")])
def test_side_lane_parts_are_means_per_rerouted_request(name, stage):
    (us0, n0, _), (us1, n1, _) = STAGES[stage]
    assert reader(name)(ctx_of()) == pytest.approx((us1 - us0) / (n1 - n0)
                                                   / 1e3)


def test_side_launch_off_cpu_adds_the_prefilter_per_rerouted_request():
    off_us = ((1_050_000 - 50_000) - (160_000 - 10_000)
              + (520_000 - 20_000) - (105_000 - 5_000))
    assert reader("sidelane.launch_offcpu_ms")(ctx_of()) == pytest.approx(
        off_us / 250 / 1e3)


def test_the_parts_lie_inside_their_stage():
    c = ctx_of()
    w = c["window"]
    assert (reader("sidelane.fetch_ms")(c)
            < w.stage_mean_ms("side_scan"))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_reads_nothing(name):
    parent = ctx_of(cpu=False, threads=False,
                    stages=name in ("dispatch.launch_offcpu_ms",))
    assert reader(name)(parent) is None


@pytest.mark.parametrize("name", NEW)
def test_an_empty_window_reads_nothing(name):
    c = {"window": scrape.Window({}, {}), "seconds": SECONDS}
    assert reader(name)(c) is None


def test_a_window_without_a_rerouted_request_reads_nothing():
    same = scrape.parse_metrics(text(0))
    c = {"window": scrape.Window(same, same), "seconds": SECONDS}
    for name in NEW:
        if name.startswith("sidelane.") or name == "dispatch.launch_offcpu_ms":
            assert reader(name)(c) is None, name
    assert reader("host.python_threads_cpu_cores")(c) == 0.0


def test_benchmark_json_adds_the_six_with_their_cells():
    import json

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in NEW:
        m = per_layer[name]
        assert (BENCH / "layer_metrics" / (name + ".py")).is_file()
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        want = (cells if name in ("dispatch.launch_offcpu_ms",
                                  "host.python_threads_cpu_cores")
                else ["crs-full-upload.upload-heavy"])
        assert m["workloads"] == want
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(NEW)
