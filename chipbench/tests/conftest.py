"""Put the repository root and ``src`` on the path for the benchmark's tests.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
