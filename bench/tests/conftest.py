"""Make ``bench`` and the program importable for the benchmark's own tests
(the repository's tests run with ``PYTHONPATH=src`` from its root)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
