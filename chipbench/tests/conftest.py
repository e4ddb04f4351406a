"""These tests are run by hand (`python -m pytest chipbench/tests -q`);
they are not part of the repository's tier-1 run. They hold JAX to the CPU
and put the Pallas kernels in interpret mode."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
