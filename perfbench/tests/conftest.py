"""Tests of the benchmark's own yardstick: `pytest perfbench/tests`.

They run on the CPU; nothing here needs or touches a chip.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
