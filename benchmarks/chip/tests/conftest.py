import sys
from pathlib import Path

# the benchmark's modules import each other by bare name, as run_cell.py
# arranges for a run
CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (str(CHIP), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
