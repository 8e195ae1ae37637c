"""The large-keys float-sum probe's two readings, on the chip, at the
probe's own size, through the job itself: ``probe_control_mesh``'s tool
(sound seeds, then THE CONTROL with the fire's dot lowered from
``Precision.HIGHEST`` to ``Precision.HIGH``, then the whole cell through
``run.py`` still lowered), pointed at the cell ``q5_large_keys_replay``.

    python benchmark/tools/probe_control_large_keys.py --seeds 1,2,3 \
        --control-seeds 4,5,6 [--harness-seed 7] [--cpu]
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT

from benchmark.tools import probe_control_mesh  # noqa: E402

if __name__ == "__main__":
    probe_control_mesh.CELL = "q5_large_keys_replay"
    sys.exit(probe_control_mesh.main())
