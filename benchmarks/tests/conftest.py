"""The benchmark's own tests: ``python -m pytest benchmarks/tests -q`` on the
CPU (not part of the repo's tier-1 suite)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
