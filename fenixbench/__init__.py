"""Chip benchmark of the FENIX replay path (see ``BENCHMARK.json``).

Importing the package puts the checkout's ``src/`` on ``sys.path``, so
``python3 -m fenixbench.run`` finds the program from the checkout root.
"""

import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)
