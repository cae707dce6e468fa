import os
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# runs of the harness in these tests keep no persistent compilation cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
