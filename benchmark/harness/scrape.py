"""Reading the program's counters: `/metrics` and the sidecar's status.

A scrape is a dict {(name, ((label, value), ...)): number}.  A window's
reading is the difference of two scrapes taken while nothing is in
flight.  The arithmetic for stage means is `bench.py
scrape_stage_breakdown`'s (`_sum` over `_count`), on differences.
"""

from __future__ import annotations

import json
import re
from typing import Dict, Optional

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> Dict[tuple, float]:
    out: Dict[tuple, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        try:
            number = float(value)
        except ValueError:
            continue
        key = (name, tuple(sorted(_LABEL.findall(labels or ""))))
        out[key] = number
    return out


def parse_sidecar(text: str) -> Dict[tuple, float]:
    """The sidecar's --status-port JSON, its top-level counters only."""
    doc = json.loads(text)
    return {("sidecar." + k, ()): float(v) for k, v in doc.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


class Window:
    """Two scrapes around a window."""

    def __init__(self, before: Dict[tuple, float], after: Dict[tuple, float]):
        self.before, self.after = before, after

    def _match(self, scrape, name, labels):
        want = set(labels.items())
        return [v for (n, ls), v in scrape.items()
                if n == name and want <= set(ls)]

    def delta(self, name: str, **labels) -> float:
        """The window's difference, summed over every series of `name`
        that carries `labels`; 0 where the series does not exist."""
        return (sum(self._match(self.after, name, labels))
                - sum(self._match(self.before, name, labels)))

    def delta_unlabelled(self, name: str) -> float:
        """The difference of the one series of `name` with no label (an
        aggregate that leads its labelled twins)."""
        return (self.after.get((name, ()), 0.0)
                - self.before.get((name, ()), 0.0))

    def last(self, name: str, **labels) -> Optional[float]:
        got = self._match(self.after, name, labels)
        return got[0] if got else None

    def labelled(self, name: str, label: str) -> Dict[str, float]:
        """{label value: window's difference} for each series of `name`."""
        out: Dict[str, float] = {}
        for (n, ls), v in self.after.items():
            if n != name:
                continue
            d = dict(ls)
            if label in d:
                out[d[label]] = out.get(d[label], 0.0) + v - self.before.get(
                    (n, ls), 0.0)
        return out

    def stage_mean_ms(self, stage: str) -> Optional[float]:
        """Mean of `ipt_stage_us{stage=...}` over the window, in ms."""
        n = self.delta("ipt_stage_us_count", stage=stage)
        if n <= 0:
            return None
        return self.delta("ipt_stage_us_sum", stage=stage) / n / 1e3

    def stage_count(self, stage: str) -> float:
        return self.delta("ipt_stage_us_count", stage=stage)
