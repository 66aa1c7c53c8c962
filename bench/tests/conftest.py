"""CPU tests of the benchmark harness: ``pytest bench/tests``.

They put ``bench/`` and ``src/`` on the path, run on the CPU at tiny sizes
(``bench/tests/data``), and never ask for a chip.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def data_dir():
    return DATA
