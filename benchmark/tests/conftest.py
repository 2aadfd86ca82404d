"""The benchmark's own tests run on the CPU at a tiny size: host route
(the program's TMTPU_DISABLE_TPU switch, set here and never by the
harness), so no kernel compiles for the wrong backend."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TMTPU_DISABLE_TPU", "1")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
