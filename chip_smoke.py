"""Smoke test: one federation round on a TPU chip against a float64 solve.

    python chip_smoke.py              # phases a-d on one chip
    python chip_smoke.py --chips 4    # the mesh-transport round on four

Runs from the root of a checkout; the phases live in
``src/repro/launch/smoke.py`` (see its docstring). Exits non-zero, before
printing its last line, when JAX finds no TPU or any phase misses its
bound; on success the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.smoke import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
