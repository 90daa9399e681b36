"""The benchmark's own tests: CPU only, run as
`python -m pytest benchmark/tests -q` from the checkout's root."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
