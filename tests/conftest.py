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

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _spans_end_with_their_module():
    """The phase spans the program keeps are process-wide, and an xdist
    worker runs one test file after another in one process: a file that
    reads them (``tests/benchmark``'s toy rehearsals bound their set-up
    by the process's start, and expect the pipeline's root spans to
    cover what is named) must not find what the files before it opened
    outside any pipeline.  Which files share a worker is the
    scheduler's choice, so every file leaves none behind."""
    yield
    from nnstreamer_tpu.utils import profile

    profile.clear()
