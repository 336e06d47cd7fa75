"""The benchmark's own tests, run by hand: ``python -m pytest bench/tests``.

The files of ``bench/`` import each other by name, as ``bench/run.py`` runs
them; the program is under ``src/``."""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
