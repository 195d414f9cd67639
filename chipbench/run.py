#!/usr/bin/env python3
"""One run of one benchmark cell on the TPU chips of this machine.

    python chipbench/run.py --workload robertson_mesh.bulk --seed 7 \
        --seconds 30 --trace 0

The cell, its deployment and its traffic are found by name from
``BENCHMARK.json`` at the root of the checkout.  The run exits nonzero,
and prints no result, when no TPU (or too few chips) is found.  Its
last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` the trace's ``breakdown``), then ``checks``: each number
compared with the plain reference beside its limit.
"""
import time

PROCESS_START = time.monotonic()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    from chipbench import harness
    sys.exit(harness.main(sys.argv[1:], root=ROOT, t_start=PROCESS_START))
