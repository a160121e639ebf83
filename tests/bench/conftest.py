"""The benchmark's modules import each other by file name, as
``bench/run.py`` runs them; put ``bench/`` and ``src/`` on the path."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
