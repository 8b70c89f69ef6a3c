"""The benchmark's own tests run on the CPU at tiny sizes (Pallas in
interpret mode), with four host devices for the sharded rehearsal;
nothing here needs or touches a chip."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        _FLAGS + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
