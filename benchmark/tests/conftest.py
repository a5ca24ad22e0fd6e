"""The benchmark's own tests: CPU, toy sizes, never a device metric.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They live with the benchmark (not under ``tests/``) so that no later PR can
edit them away. Four virtual CPU devices stand in for a 2x2 slice."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
