"""Bytes the device program of an unbounded keyed join must move for
one batch, from the deployment's shapes (``configs/<module>.py``
``step_shapes``: records a batch, distinct keys a batch and those whose
result changes, by the generator's formulas), not from the program's
counters: a share then reads the same work whatever implements it. The
peaks table is ``step_bytes.load_peaks``'s.

``apply_bytes``: the program that folds a batch of both sides into the
join's state and writes the changelog of the keys it touched (once a
batch). The least it must move: the upload, four int32 words a record
(slot and side, event-time offset, price or expires, category), read
once; for each DISTINCT key of the batch one read and one write of the
64 bytes that hold its state (nine words, 36 bytes: two of the 32-byte
granules the chip moves); and for each key whose result changes its
changelog entry, five int32 words (slot, category, result before and
after, newest event time), written once. The sorts, the scans and the
broadcast of the left row to its key's records are work the byte model
does not charge: the share says how far the program is from a pass over
its input and its keys.
"""
from __future__ import annotations

UPLOAD_BYTES_PER_RECORD = 16    # int32 slot+side, time, value, category
KEY_BYTES = 64                  # 36 bytes of state: two granules
ENTRY_BYTES = 20                # five int32 words a changelog entry


def apply_bytes(*, records: int, keys: int, changed: int, **_shapes) -> int:
    """The least one apply program must move for a batch."""
    return (int(records) * UPLOAD_BYTES_PER_RECORD
            + int(keys) * 2 * KEY_BYTES + int(changed) * ENTRY_BYTES)
