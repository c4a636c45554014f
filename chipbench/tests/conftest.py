"""The benchmark's own tests: CPU only, at tiny sizes.  Not part of the
repository's test suite (``tests/``); run them with
``python -m pytest chipbench/tests``."""

import os
import sys
import tempfile
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compiled programs of these runs go to a cache of their own
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="chipbench-tests-"))

TINY = 16
SEED = 2**33 + 12345


def tiny(config: dict) -> dict:
    """Configuration keys that cut a cell to ``TINY`` cells a side."""
    out = {"cells_per_side": TINY,
           "coefficient": {**config["coefficient"], "modes": TINY}}
    if "service" in config:
        out["service"] = {**config["service"], "max_batch": 4,
                          "buckets": [1, 2, 4]}
    return out
