"""Put the program (src/) and the benchmark's own modules on the path."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
for p in (CHIP.parents[1] / "src", CHIP, CHIP / "models"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
