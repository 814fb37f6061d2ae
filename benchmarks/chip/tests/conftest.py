import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[2] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
