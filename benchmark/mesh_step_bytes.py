"""Bytes ONE device of a mesh has to move in one sharded step of a
host-fed keyed window count (``ops/window.py`` ``apply_shard_split``
under ``shard_map``), from shapes. Beside ``step_bytes.py``, whose model
is the one-chip pair upload; the peaks table is the same
(``step_bytes.load_peaks``).

The sharded step, per device, for a batch of ``records`` cut into
``devices`` arrival blocks of ``block = records / devices`` entries:

- upload: its block of the split-encoded batch, read once: a uint16 key
  slot and a uint8 ring column, 3 bytes a record (padding included: the
  host pads a batch to the block layout before it encodes).
- exchange, send side: the block is bucketed by owner into ``devices``
  buckets of capacity ``block`` (a bucket can never overflow), each
  entry one int32 word and one validity byte: written once, read once
  by the ``all_to_all``.
- exchange, receive side: the same shape arrives, written by the
  ``all_to_all`` and read once by the scatter.
- pane state: this device's share (``state_bytes``: the program's
  ``memory.hbm_state_bytes`` gauge is PER DEVICE under a mesh) read and
  written once; a step that fires reads it a third time.

This is the least the algorithm as written needs; the bucketing's sort
and the scatter move more. A step that takes longer than ``bytes /
peak`` is bound by something else, which is what the share says.
"""
from __future__ import annotations

UPLOAD_BYTES_PER_RECORD = 3      # uint16 slot + uint8 ring column
EXCHANGE_BYTES_PER_ENTRY = 4 + 1  # int32 packed (slot, column) + bool


def mesh_step_bytes(*, records: int, devices: int, state_bytes: int,
                    fires: bool) -> int:
    devices = int(devices)
    block = -(-int(records) // devices)       # entries a device uploads
    upload = block * UPLOAD_BYTES_PER_RECORD
    buffer = devices * block * EXCHANGE_BYTES_PER_ENTRY
    send = 2 * buffer     # bucketed (written), sent (read)
    receive = 2 * buffer  # received (written), scattered (read)
    return upload + send + receive + (3 if fires else 2) * int(state_bytes)
