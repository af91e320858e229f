"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (configuration, job mix, chips) is looked up by name in
``BENCHMARK.json`` at the root of the checkout.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last the numbers compared with their limits (``checks``).  Without a TPU,
or with fewer chips than the cell asks for, it prints no result and exits
with code 3.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
