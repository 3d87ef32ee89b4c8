"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q -p no:cacheprovider``.

Outside ``tests/``, so the repo's tier-1 count is untouched.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
