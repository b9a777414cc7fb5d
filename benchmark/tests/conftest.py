"""The harness's self-tests: CPU only, tiny sizes. Not part of tier-1
(run ``python3 -m pytest benchmark/tests -q`` from the repo root)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
