"""The benchmark's CPU tests: the repository root on the path, and a small
spec root for driving whole runs on the CPU."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src"), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchutil import make_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
