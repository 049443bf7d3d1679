import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(CHIP.parents[1] / "src"))
