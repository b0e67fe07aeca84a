"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload mnist-l2.fit --seed 7 --seconds 30 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each number compared beside its
limit; the checks are also the last lines of standard error.  Off a TPU
the run exits 2 and prints no result; ``--rehearse`` runs the cell at a
tiny size on any device and exits 1, also without a result.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

# libtpu writes its logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
