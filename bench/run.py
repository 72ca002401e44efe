#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for; it exits non-zero and prints no result where JAX finds no
TPU, or fewer chips.  Set-up (weights made from ``--seed`` on the device,
every shape of the cell compiled and warmed) counts as ``setup_s``; the
window then measures for ``--seconds``.  With ``--trace 1`` the window is
traced and the result carries the cell's per-layer metrics, the device's
busy and window seconds, and a breakdown; with ``--trace 0`` its
end-to-end metrics.  After the window the plain reference checks what the
timed path produced.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown``), with the compared numbers and their limits under
``checks``, last; the same numbers are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench.harness.runner import main
    sys.exit(main(ROOT, sys.argv[1:], T_START))
