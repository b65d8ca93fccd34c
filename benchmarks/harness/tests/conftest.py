"""The harness's own tests: CPU only, four virtual devices (for the mesh
rehearsal), the harness directory importable. Run them with

    python3 -m pytest benchmarks/harness/tests -q
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

HARNESS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HARNESS)
