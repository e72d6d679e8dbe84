"""The benchmark's own tests run on the CPU, at sizes a test run holds:
``python3 -m pytest benchmark/tests -q``."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
