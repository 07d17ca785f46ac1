import os
import sys

# Tests run on the CPU backend (Pallas kernels in interpret mode); the TPU
# is reached only through chip_smoke.py. Subprocess tests force their own
# host device counts.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Property tests use hypothesis (declared in pyproject's test extra and
# installed in CI). If the local runtime lacks it, fall back to the
# deterministic stub so the suite still collects and runs.
try:
    import hypothesis  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.dirname(__file__))
    import _hypothesis_stub

    _hypothesis_stub.install()
