"""Test harness config: run on CPU with 8 virtual devices so multi-chip
sharding paths are exercised without TPU hardware (the driver separately
dry-runs the multichip path; the benchmark runs on the real chip)."""

import os
import sys

# Must be set before jax initializes its backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# In-tree convenience only: an installed nnstreamer_tpu wins, so the
# suite also validates `pip install .` copies (run pytest from anywhere).
try:
    import nnstreamer_tpu  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
