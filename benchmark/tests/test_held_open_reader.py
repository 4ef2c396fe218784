"""`confirm.held_open_share`'s reader (ISSUE 33), on a recorded pair of
scrapes.

`data/held_open_scrape_{before,after}.txt` are two `/metrics` scrapes of
a CPU batcher (one lane, two confirm workers, a closed loop of 8) 0.7 s
apart, cut to the series the reader reads.  An arithmetic fixture:
nothing here is a device number.
"""

import json
from pathlib import Path

import pytest

from harness import scrape
from test_substage_readers import reader

BENCH = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"
NAME = "confirm.held_open_share"


def window(before: str, after: str) -> dict:
    return {"window": scrape.Window(scrape.parse_metrics(before),
                                    scrape.parse_metrics(after)),
            "seconds": 0.7}


def test_held_share_of_the_recorded_window():
    ctx = window((DATA / "held_open_scrape_before.txt").read_text(),
                 (DATA / "held_open_scrape_after.txt").read_text())
    held, direct = 78 - 23, 16 - 4
    assert reader(NAME)(ctx) == pytest.approx(100.0 * held / (held + direct))


@pytest.mark.parametrize("before,after,want", [
    # every cycle resolved direct (an inline confirm, lone requests)
    ('ipt_cycles_total{confirm="held"} 0\n'
     'ipt_cycles_total{confirm="direct"} 10\n',
     'ipt_cycles_total{confirm="held"} 0\n'
     'ipt_cycles_total{confirm="direct"} 50\n', 0.0),
    # no cycle in the window: no share to speak of
    ('ipt_cycles_total{confirm="held"} 4\n'
     'ipt_cycles_total{confirm="direct"} 6\n',
     'ipt_cycles_total{confirm="held"} 4\n'
     'ipt_cycles_total{confirm="direct"} 6\n', None),
    # a program without the counter (the parent of PR 33)
    ('ipt_batches_total 10\n', 'ipt_batches_total 50\n', None),
])
def test_edges(before, after, want):
    assert reader(NAME)(window(before, after)) == want


def test_benchmark_json_entry():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "confirm",
        "moves": "verdicts_per_s",
        "workloads": [w["name"] for w in bench["workloads"]]}
