import os
import sys

# Tests run single-device CPU (the dry-run sets its own 512-device flag in a
# subprocess).  Keep any preexisting XLA_FLAGS but never force device count
# here — smoke tests and benches must see 1 device.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The planted-violation trees under fixtures/ contain deliberately broken
# "tests" (lint fodder for repro.analysis) — never collect them.
collect_ignore_glob = ["fixtures/*"]
