"""Bytes the device programs of a host-fed keyed window count must move
when the key space is too large for the pre-aggregated upload, from
shapes. Beside ``step_bytes.py`` (the one-chip pair upload, which charges
every step two passes over the whole state) and ``mesh_step_bytes.py``;
the peaks table is the same (``step_bytes.load_peaks``).

At 16.8 M key slots the batch reaches the device record by record
(``ops/window.py`` ``apply_kernel``) and three programs touch the pane
state, each of its own:

- ``apply_bytes``: the apply program (``jit_apply_kernel``, once a
  batch) adds ``records`` counts into a DONATED state tensor: it need
  not pass over the state, only reach the cells it adds to. The least it
  must move: the upload, one packed int32 (slot x ring + column) a
  record, read once; and for each record one read and one write of the
  32-byte granule that holds its cell (the smallest piece of HBM the
  chip moves). Records of one key and pane share a granule, so a batch
  whose bids pile on few cells needs less; this charges every record
  its own, the most a scatter of ``records`` counts can need, and the
  share is then an upper bound on how near the program is to it.
- ``fire_bytes``: a fire program (``jit_ring_append_topn_kernel``, once
  a window end that falls due) sums each window's panes for every row
  and picks the top: it must read the pane state once,
  ``state_bytes`` (the program's ``memory.hbm_state_bytes``: rows x
  ring columns x 4 bytes; the chip's layout pads the ring columns, which
  the program need not have read). What it writes is a few rows.

The purge (``jit_clear_kernel``) rides in a program of its own here and
has no share: it is reported as time a batch. In the fused step of the
small-state cells apply, fire and clear are ONE program
(``jit_fused_step_kernel``), and these shares are not read there.
"""
from __future__ import annotations

UPLOAD_BYTES_PER_RECORD = 4     # packed int32: slot * ring + column
GRANULE_BYTES = 32              # smallest HBM access the chip makes


def apply_bytes(*, records: int) -> int:
    """The least one apply program must move for ``records`` records."""
    return int(records) * (UPLOAD_BYTES_PER_RECORD + 2 * GRANULE_BYTES)


def fire_bytes(*, state_bytes: int) -> int:
    """The least one fire program must move: the pane state, read once."""
    return int(state_bytes)
