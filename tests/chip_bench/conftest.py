"""Tests of the chip benchmark's harness (benchmarks/chip).  They run on the
CPU in tier 1; nothing here measures anything."""

import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(CHIP_DIR) not in sys.path:
    sys.path.insert(0, str(CHIP_DIR))
