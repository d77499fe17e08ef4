"""Make the benchmark's package importable as ``chipbench``."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))
