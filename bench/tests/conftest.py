"""CPU-only tests of the benchmark's yardstick.  They put ``bench`` and
``src`` on the path and never touch a TPU."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))
