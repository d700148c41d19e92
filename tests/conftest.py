"""Pytest bootstrap: make src/ and the tests dir importable everywhere.

Keeps `PYTHONPATH=src python -m pytest` (the tier-1 command) and a bare
`pytest` invocation equivalent, and lets test modules import helpers from
one another regardless of pytest's import mode.
"""

import os
import sys

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(os.path.dirname(_HERE), "src")
for p in (_HERE, _SRC):
    if p not in sys.path:
        sys.path.insert(0, p)
