"""Bytes the one device program that checkpointing adds must move, from
shapes. Beside ``large_keys_step_bytes.py`` (apply, fire); the peaks
table is the same (``step_bytes.load_peaks``).

A checkpoint's freeze clones the pane tensors on the device
(``ops/window.py`` ``snapshot_clone_kernel``, the program
``jit_snapshot_clone_kernel``): later steps donate the state's buffers,
so the snapshot needs buffers of its own. A copy reads every byte once
and writes it once, AS THE DEVICE LAYS THE TENSOR OUT: a v5e keeps an
int32 ``[rows, ring]`` tensor of few columns column-major in tiles of
8 x 128 (``s32[rows, ring]{0,1:T(8,128)}``, read from the compiled
programs in PR 32), so the ring columns are padded to a multiple of 8
and the rows to a multiple of 128. That padding is part of what the
copy moves, which is why the share is taken over the laid-out bytes and
not over ``memory.hbm_state_bytes``: 16,777,217 rows x 12 columns are
805,306,416 bytes of counts and 1,073,750,016 bytes of HBM.
"""
from __future__ import annotations

TILE_ROWS = 128         # the minor dimension of a tile: rows here
TILE_COLUMNS = 8
CELL_BYTES = 4          # int32 counts; a float32 lane is as wide


def up_to(n: int, multiple: int) -> int:
    return -(-int(n) // multiple) * multiple


def laid_out_bytes(*, rows: int, ring: int, lanes: int = 1) -> int:
    """HBM bytes of the pane tensors: ``lanes`` tensors of ``[rows,
    ring]`` 4-byte cells (the count lane alone for a COUNT)."""
    return (int(lanes) * up_to(rows, TILE_ROWS) * up_to(ring, TILE_COLUMNS)
            * CELL_BYTES)


def clone_bytes(*, rows: int, ring: int, lanes: int = 1) -> int:
    """The least one clone must move: every laid-out byte read once and
    written once."""
    return 2 * laid_out_bytes(rows=rows, ring=ring, lanes=lanes)
