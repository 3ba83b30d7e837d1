"""Tests of the benchmark itself, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
