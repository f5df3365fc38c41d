"""One run of one chip benchmark cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. It prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics) and
``device``; then ``window_compiles`` (programs compiled or loaded inside
the window) and ``checks`` (each number compared, with its limit), which
are also the last lines of standard error. Without a TPU, or without the
program under ``src/``, it prints no result and exits non-zero.
"""
import time

T_START = time.perf_counter()

import sys                                    # noqa: E402
from pathlib import Path                      # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import main            # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
