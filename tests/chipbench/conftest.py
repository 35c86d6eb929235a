"""Fixtures of the chip benchmark's tests."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, Path(__file__).resolve().parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench_fixtures import make_tiny_bench  # noqa: E402


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_bench(tmp_path)
