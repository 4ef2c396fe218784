"""The side lane's batch reader (`sidelane.requests_per_batch`) on
synthetic scrapes: the window's differences are set by hand below.  A
scrape without `ipt_oversized_batches_total` stands for a program that
serves each rerouted request alone and does not render it: the reader
reads nothing there.  Arithmetic fixtures: nothing here is a device
number."""

import importlib.util
import json
from pathlib import Path

import pytest

from harness import scrape

BENCH = Path(__file__).resolve().parent.parent
NAME = "sidelane.requests_per_batch"


def reader():
    path = BENCH / "layer_metrics" / (NAME + ".py")
    spec = importlib.util.spec_from_file_location(NAME.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def text(raw: int, unpack: int, batches=None) -> str:
    lines = ['ipt_oversized_rerouted_total{kind="raw"} %d' % raw,
             'ipt_oversized_rerouted_total{kind="unpack"} %d' % unpack,
             "ipt_requests_total %d" % (3 * (raw + unpack))]
    if batches is not None:
        lines.append("ipt_oversized_batches_total %d" % batches)
    return "\n".join(lines) + "\n"


def ctx_of(before: str, after: str):
    return {"window": scrape.Window(scrape.parse_metrics(before),
                                    scrape.parse_metrics(after)),
            "seconds": 20.0}


@pytest.mark.parametrize("batches,want", [(250, 1.0), (125, 2.0),
                                          (100, 2.5), (50, 5.0)])
def test_rerouted_requests_of_both_kinds_over_the_batches(batches, want):
    c = ctx_of(text(10, 30, 40), text(60, 230, 40 + batches))
    assert reader()(c) == pytest.approx(want)


def test_a_program_without_the_batch_counter_reads_nothing():
    assert reader()(ctx_of(text(10, 30), text(60, 230))) is None


def test_a_window_without_a_batch_reads_nothing():
    same = text(10, 30, 20)
    assert reader()(ctx_of(same, same)) is None
    assert reader()({"window": scrape.Window({}, {}),
                     "seconds": 20.0}) is None


def test_benchmark_json_lists_it_for_the_upload_cell():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    m = bench["per_layer"][-1]
    assert m == {"name": NAME, "unit": "req/batch", "better": "higher",
                 "source": "program_counter",
                 "layer": "oversized side lane", "moves": "verdicts_per_s",
                 "workloads": ["crs-full-upload.upload-heavy"]}
