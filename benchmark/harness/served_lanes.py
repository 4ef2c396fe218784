"""`served.py` for a configuration that serves one lane per device
(`--lanes auto`): the same program through the same wrapper.

On the chip this adds nothing.  Held to the CPU (`JAX_PLATFORMS=cpu`: a
rehearsal, a test) it asks XLA for as many virtual CPU devices as the
deployment has chips, before anything imports JAX, so `--lanes auto`
finds a lane for each and `run.py`'s `device_count >= chips` holds.
"""

import os
import runpy
from pathlib import Path

#: the chips of the deployment's host (configs/crs-full-4lane.json)
CHIPS = 4

if __name__ == "__main__":
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % CHIPS).strip()
    runpy.run_path(str(Path(__file__).with_name("served.py")),
                   run_name="__main__")
