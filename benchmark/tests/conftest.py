"""Tests of the benchmark's own yardstick. They run on the CPU backend
(``JAX_PLATFORMS=cpu``): results and counts, never a time or a rate."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (HERE, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
