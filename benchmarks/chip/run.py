#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), and last of all ``checks``: each number
compared with the plain reference beside its limit.  Exits non-zero, and
prints no result, when JAX finds no TPU or fewer chips than the cell asks
for.  See ``harness.py``.
"""
import os
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
# in place of this script's own directory, whose trace.py would shadow the
# standard library's
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's logs go under this run's TMPDIR, not a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
