import os
import sys

# tests run against the single real CPU device; multi-device meshes are
# exercised via a subprocess (test_distributed.py) so XLA_FLAGS stays unset
# here.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
