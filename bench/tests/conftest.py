"""The benchmark's own checks: ``pytest bench/tests`` from the checkout.

They run on the CPU at small sizes and import the program from ``src``."""
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
