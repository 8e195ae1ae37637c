"""Bytes the device program of an unwindowed keyed aggregation must
move for one batch, from the deployment's shapes (``configs/<module>.py``
``step_shapes``: records a batch, distinct keys a batch by the
generator's formulas), not from the program's counters: a share then
reads the same work whatever implements it. The peaks table is
``step_bytes.load_peaks``'s.

``apply_bytes``: the program that folds a batch into the accumulators
and gathers the rows of the keys it touched (once a batch). The least
it must move: the upload, an int32 slot, an int32 event-time offset and
an int32 price a record, read once; for each DISTINCT key of the batch
one read and one write of the 64 bytes that hold its accumulators (nine
words, 36 bytes: two of the 32-byte granules the chip moves); and the
emitted row, ten int32 words (slot, count, three band counts, min, max,
the sum's two words, the newest bid), written once. The sort and the
scans between them are work the byte model does not charge: the share
says how far the program is from a pass over its input and its keys.
"""
from __future__ import annotations

UPLOAD_BYTES_PER_RECORD = 12    # int32 slot + event-time offset + price
KEY_BYTES = 64                  # 36 bytes of accumulators: two granules
ROW_BYTES = 40                  # ten int32 words an emitted row


def apply_bytes(*, records: int, keys: int, **_shapes) -> int:
    """The least one apply program must move for a batch."""
    return (int(records) * UPLOAD_BYTES_PER_RECORD
            + int(keys) * (2 * KEY_BYTES + ROW_BYTES))
