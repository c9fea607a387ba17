"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
chipbench/tests -q``.  Not part of the repo's tier-1 tests."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
