"""Test bootstrap: force CPU with 8 virtual devices.

This is the kind-cluster analog from SURVEY.md §4: multi-chip sharding
logic is exercised on a virtual 8-device CPU mesh so CI needs no TPU.
The platform-forcing recipe lives in ingress_plus_tpu/utils/platform.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ingress_plus_tpu.utils.platform import force_cpu_devices  # noqa: E402

force_cpu_devices(8)
