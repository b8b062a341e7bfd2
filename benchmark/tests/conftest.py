"""The benchmark's own tests: ``python -m pytest benchmark/tests`` from the
root of the repo, on the CPU. They are not part of the repo's tier-1 run."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
