"""The benchmark's own tests run on the CPU, with the Pallas kernels in
interpret mode; nothing here measures anything.

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_FUSED", "always")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
