"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  It exits non-zero, with no result, where JAX finds no TPU or
fewer chips than the cell needs.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``) and, last, ``checks``: each number
the comparison read, beside its limit.
"""

import time

T0 = time.time()   # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
