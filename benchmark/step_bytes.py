"""Bytes one device step of a host-fed window job has to move, from
shapes, and the table of peaks it is held against.

The step of a host-fed keyed window count (``ops/window.py``): the host
pre-aggregates a batch to one (key slot, pane) pair per distinct pair,
uploads that buffer, and the step program adds it into the pane-state
tensor (read + written once) and, when window ends are due, reads the
panes of those windows once more for the fire. Assumed shapes, written
here because the number rests on them:

- upload: one int32 word per distinct (slot, pane) pair of the batch,
  padded to the next power of two (at least 256), plus the 512-byte
  fused header. Distinct pairs are bounded by ``min(records, keys x
  panes the batch spans)``.
- pane state: ``state_bytes`` as the program's ``memory.hbm_state_bytes``
  gauge reports it (rows x ring x 4 bytes per lane); the step reads and
  writes it once: ``2 x state_bytes``; a step that fires reads it a
  second time: ``+ state_bytes``.

This is the least the algorithm as written needs; a step that takes
longer than ``bytes / peak`` is bound by something else (sort, top-k,
launch latency), which is what the share says.
"""
from __future__ import annotations

import json
import os

HEADER_BYTES = 512
PAIR_BYTES = 4


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def step_bytes(*, records: int, keys: int, panes_per_batch: int,
               state_bytes: int, fires: bool) -> int:
    pairs = min(int(records), int(keys) * int(panes_per_batch))
    upload = next_pow2(max(pairs, 256)) * PAIR_BYTES + HEADER_BYTES
    return upload + (3 if fires else 2) * int(state_bytes)


def load_peaks(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}: add a row with its source")
    return table[device_kind]
