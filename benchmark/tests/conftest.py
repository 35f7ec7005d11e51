"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q`` (under a minute; not part of tier-1)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
