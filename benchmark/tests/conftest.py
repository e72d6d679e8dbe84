"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``python3 -m pytest benchmark/tests -q``. The CPU backend gets 8 virtual
devices unless ``XLA_FLAGS`` names a count, so that a corpus and an index
sharded over four devices run here."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
