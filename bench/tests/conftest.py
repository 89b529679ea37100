"""bench/tests: run by hand with `python -m pytest bench/tests -q` (not part
of tier-1, which stays tests/)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAXMC_LEDGER", "off")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
