"""Where things are: the checkout root, the program's sources, the
benchmark's own data."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SRC = ROOT / "src"


def add_src() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
