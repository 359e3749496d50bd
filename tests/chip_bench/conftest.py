"""Tests of the chip benchmark's harness (benchmarks/chip).  They run on the
CPU in tier 1; nothing here measures anything."""

import json
import os
import shutil
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
if str(CHIP_DIR) not in sys.path:
    sys.path.insert(0, str(CHIP_DIR))


def cpu_env(**extra: str) -> dict[str, str]:
    """The environment of a child that runs the harness on the CPU: one CPU
    device, as a worker has (the suite's own XLA_FLAGS asks for eight)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def copy_of_the_benchmark(root: Path) -> tuple[Path, dict]:
    """A copy of the benchmark under ``root`` as a later PR's checkout
    would hold it — ``benchmarks/chip`` (without what runs left behind), the
    program as a link — and BENCHMARK.json's content for the caller to add
    entries to and write to ``root / "BENCHMARK.json"``."""
    chip = root / "benchmarks" / "chip"
    shutil.copytree(CHIP_DIR, chip, ignore=shutil.ignore_patterns(
        "_run", "__pycache__"))
    repo = CHIP_DIR.parents[1]
    os.symlink(repo / "crowdllama_tpu", root / "crowdllama_tpu")
    return chip, json.loads((repo / "BENCHMARK.json").read_text())
