"""The benchmark's own tests, run by path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

(The repository's tier-1 suite collects ``tests/`` only.)"""
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
