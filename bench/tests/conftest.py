"""Tests of the benchmark's own code, on the CPU at tiny sizes.

  JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parent / "tools", HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
