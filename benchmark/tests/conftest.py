"""The benchmark's own tests: CPU only and light. Run with
``python -m pytest benchmark/tests -q`` from the root of the repo."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
