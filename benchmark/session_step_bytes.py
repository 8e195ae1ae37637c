"""Bytes the two device programs of a keyed session count must move,
from the deployment's shapes (``configs/<module>.py`` ``step_shapes``:
records a batch, distinct keys a batch by the generator's formulas, key
slots, session lanes a slot), not from the program's counters: a share
then reads the same work whatever implements it. The peaks table is
``step_bytes.load_peaks``'s.

- ``apply_bytes``: the program that folds a batch into the session
  state (once a batch). The least it must move: the upload, an int32
  slot and an int32 timestamp a record, read once; and for each
  DISTINCT key of the batch one read and one write of the 32-byte
  granule that holds its session (start, last, count: 12 bytes; the
  smallest piece of HBM the chip moves). The sort between the two is
  work the byte model does not charge: the share says how far the
  program is from a pass over its input and its keys.
- ``fire_bytes``: the program that fires the sessions a watermark
  completes (once an advance, more when more rows are due than a pass
  holds). It must look at every lane of every slot: ``last`` and
  ``count``, 8 bytes a lane, read once; and write the fired rows, four
  int32 each (in a steady stream as many sessions close in a batch as
  open: ``keys``).
"""
from __future__ import annotations

UPLOAD_BYTES_PER_RECORD = 8     # int32 slot + int32 timestamp
GRANULE_BYTES = 32              # smallest HBM access the chip makes
LANE_SCAN_BYTES = 8             # last + count of one session lane
ROW_BYTES = 16                  # slot, start, last, count


def apply_bytes(*, records: int, keys: int, **_shapes) -> int:
    """The least one apply program must move for a batch."""
    return (int(records) * UPLOAD_BYTES_PER_RECORD
            + int(keys) * 2 * GRANULE_BYTES)


def fire_bytes(*, slots: int, lanes: int, keys: int, **_shapes) -> int:
    """The least one fire pass must move."""
    return int(slots) * int(lanes) * LANE_SCAN_BYTES + int(keys) * ROW_BYTES
