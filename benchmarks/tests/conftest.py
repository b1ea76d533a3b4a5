"""The benchmark's own tests: CPU, seconds, no chip.

    python -m pytest benchmarks/tests -q
"""

import os
import os.path as osp
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = osp.dirname(osp.dirname(osp.dirname(osp.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
