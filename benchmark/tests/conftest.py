"""The benchmark's own tests run on the CPU: ``python3 -m pytest benchmark/tests``."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
