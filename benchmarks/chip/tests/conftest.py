"""Put the benchmark's own code and the program on the path."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
