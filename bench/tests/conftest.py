"""The benchmark's own tests: CPU only, small sizes.  The checkout's root
goes on the path so that `bench.lib` imports as it does under run.py."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
