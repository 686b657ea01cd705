"""The benchmark's own CPU tests (not part of the repo's tier-1 run):

  JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
