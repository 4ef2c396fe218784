"""Run with `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q` from the
root of a checkout.  No chip is needed; nothing here is a device number."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: starts real processes (a minute or so each)")
