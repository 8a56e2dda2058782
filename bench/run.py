#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer ones), ``device`` and, last, ``checks`` (each number compared
with the plain reference beside its limit; also the last lines of stderr).
Where JAX finds no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

import os
import sys

# run from the checkout root: import ``bench`` as a package (its ``trace``
# module must not shadow the standard library's) and the program from src/
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = _ROOT

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
