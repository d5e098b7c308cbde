"""Puts the benchmark harness (``benchmarks/chip``, imported as ``chip``)
on the path of its CPU tests, and gives them the small configurations."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "benchmarks"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import chip_cases  # noqa: E402


@pytest.fixture
def qwen():
    return copy.deepcopy(chip_cases.QWEN)


@pytest.fixture
def moe():
    return copy.deepcopy(chip_cases.MOE)
