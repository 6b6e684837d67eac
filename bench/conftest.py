"""Puts the checkout and ``src/`` on the path for ``pytest bench/``."""

import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
