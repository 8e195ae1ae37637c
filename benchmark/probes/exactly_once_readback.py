"""The probe of ``nexmark_q5_exactly_once``: what the timed job's
checkpoints hold, read back from disk and held to the plain reference.

After the window, outside every timing:

(a) ``float_sum_large_keys.run`` as it stands (imported), its
    ``compared`` merged in: the float lane stays guarded here too.
(b) ``checkpoints_short_of_<n>`` = max(0, n - the checkpoints the timed
    job had completed when it froze its LAST one), n the entry's
    ``checkpoints_wanted``; the count is the job's own
    ``checkpoint.completed`` as that last checkpoint's file records it
    (a job with an interval ends on a synchronous checkpoint, so every
    one before it had completed by then). Limit 0.
(c) The newest checkpoint taken in MID-STREAM (the newest but that last
    one: written by the timed path at the timed size) against the plain
    reference at its source position: the stream regenerated from the
    seed up to the recorded batch, counted per pane
    (``nexmark_q5_large_keys.pane_counts``). For every pane alive at
    that position, every slot's count equals the reference's count of
    the auction the checkpoint's directory gives the slot, every
    auction the reference counts has a slot, no auction has two, and no
    other cell of the tensor holds a count:
    ``checkpoint_cells_differing``, limit 0. The recorded max
    timestamp, the operator's watermark and its purge horizon equal
    what the reference works out from the position:
    ``checkpoint_position_mismatches``, limit 0. The end-of-input
    checkpoint must hold no key and no count.

The reference's side is numpy and json only. Nothing is taken from the
program but the files it wrote: ``read_blob`` below is this probe's own
reader of the checkpoint format (``checkpoint/blobformat.py`` describes
it: magic, a JSON header whose ``tree`` mirrors the payload with tagged
placeholders, and raw C-order arrays at 64-byte-aligned offsets), and
the directory is the one the configuration's ``build`` chose for the
timed job (``CHECKPOINT_DIRS``), removed here when it has been read.

A pane ``q`` holds the bids stamped ``[q * slide, (q + 1) * slide)``; it
is alive while the last window over it, ending at ``(q + ppw) * slide``,
has not fired; a window ending at ``E`` fires once the watermark
(max timestamp - out-of-orderness - 1) reaches ``E - 1``. Pane ``q``
lives in ring column ``q % ring``; slot ``s`` of a tensor blocked over
``n_dev`` devices is row ``s + s // slots_per_device`` (each block ends
in a row of its own that counts nothing the job reads).
"""
from __future__ import annotations

import json
import os
import struct
import time
from typing import List, Optional, Tuple

import numpy as np

from benchmark.probes import float_sum_large_keys

MAGIC = b"FTCKPT3\n"


# -- the files, read by nothing but json and numpy --------------------------

def read_blob(path: str, mode: str = "r"):
    """A checkpoint blob as the tree it encodes, arrays as views of the
    mapped file (``mode`` "r+": writable views, for the controls)."""
    raw = np.memmap(path, dtype=np.uint8, mode=mode)
    head = bytes(raw[:len(MAGIC) + 4])
    if head[:len(MAGIC)] != MAGIC:
        raise ValueError(f"{path}: not a checkpoint blob (magic)")
    hlen = struct.unpack("<I", head[len(MAGIC):])[0]
    base = len(MAGIC) + 4 + hlen
    header = json.loads(bytes(raw[len(MAGIC) + 4:base]).decode())
    arrays = []
    for spec in header["arrays"]:
        lo = base + spec["offset"]
        if lo + spec["nbytes"] > len(raw):
            raise ValueError(f"{path}: array section is cut short")
        arrays.append(raw[lo:lo + spec["nbytes"]].view(
            np.dtype(spec["dtype"])).reshape(spec["shape"]))

    def dec(v):
        if isinstance(v, list):
            return [dec(x) for x in v]
        if not isinstance(v, dict):
            return v
        if "__nd__" in v:
            return arrays[v["__nd__"]]
        if "__tup__" in v:
            return tuple(dec(x) for x in v["__tup__"])
        if "__kdict__" in v:
            return {dec(k): dec(x) for k, x in v["__kdict__"]}
        if "__np__" in v:
            return np.dtype(v["__np__"][0]).type(v["__np__"][1])
        if "__panestate__" in v:
            return {k: dec(x) for k, x in v["__panestate__"].items()}
        return {k: dec(x) for k, x in v.items()}

    return dec(header["tree"])


def list_checkpoints(root: str) -> List[Tuple[int, str, dict]]:
    """``[(checkpoint id, directory, manifest)]`` of the COMPLETE
    checkpoints under ``root`` (one job directory below it), oldest
    first: those with a manifest at their final name."""
    out = []
    for job in sorted(os.listdir(root)):
        for name in os.listdir(os.path.join(root, job)):
            d = os.path.join(root, job, name)
            mf = os.path.join(d, "MANIFEST.json")
            if name.startswith("chk-") and ".inprogress." not in name \
                    and os.path.isfile(mf):
                with open(mf) as f:
                    m = json.load(f)
                out.append((int(m["checkpoint_id"]), d, m))
    return sorted(out, key=lambda c: c[0])


def load_checkpoint(d: str, manifest: dict) -> Tuple[dict, dict]:
    """``(meta, the window operator's snapshot)`` of one checkpoint:
    every file the manifest names is read; the operator is the one
    whose snapshot has pane tensors and a key directory."""
    if manifest.get("compression", "none") != "none":
        raise ValueError(f"{d}: compressed; the configuration asks none")
    meta = read_blob(os.path.join(d, "meta.blob"))
    ops = [read_blob(os.path.join(d, entry["file"]))
           for _nid, entry in sorted(manifest["ops"].items())]
    windows = [o for o in ops if isinstance(o, dict)
               and "panes" in o and "directory" in o]
    if len(windows) != 1:
        raise ValueError(f"{d}: {len(windows)} window operators")
    return meta, windows[0]


def position_of(meta: dict) -> int:
    """The source position: batches handed over (one source, one split)."""
    (splits,) = meta["sources"].values()
    (pos,) = splits.values()
    return int(pos)


# -- the reference at a position --------------------------------------------

class Reference:
    """What the plain reference says of the state after ``position``
    batches of the stream ``(pool, schedule)``: the max timestamp, the
    watermark, the panes alive and their counts."""

    def __init__(self, module, pool, schedule, batch: int, p: dict,
                 position: int) -> None:
        slide, ppw = int(p["slide_ms"]), int(p["window_ms"]) // int(
            p["slide_ms"])
        self.position = position
        self.max_ts = int(schedule.batch_ts(position - 1, batch)[-1])
        self.watermark = self.max_ts - module.fire_delay_ms(p) - 1
        self.first_alive = (self.watermark + 1) // slide - ppw + 1
        self.alive = range(max(self.first_alive, 0),
                           self.max_ts // slide + 1)
        # event ids are stamped id // events_per_ms: the first batch
        # that can hold an event of the first pane alive
        first = max(self.first_alive, 0) * slide * schedule.events_per_ms \
            // batch
        self.batches = position - first
        self.panes = module.pane_counts(
            ((pool[i % len(pool)], schedule.batch_ts(i, batch))
             for i in range(first, position)), slide)


def rows_of_slots(n_slots: int, n_dev: int) -> np.ndarray:
    slots = np.arange(n_slots, dtype=np.int64)
    return slots + slots // (n_slots // n_dev)


def cells_differing(snap: dict, ref: Reference) -> int:
    """Cells of the checkpoint's pane tensor that the reference counts
    otherwise, auctions the reference counts that have no slot or two,
    and counts anywhere else in the tensor."""
    counts = snap["panes"]["counts"]
    ring = counts.shape[1]
    used = np.asarray(snap["directory"]["rev_used"], bool)
    keys = np.asarray(snap["directory"]["rev_keys"], np.int64)
    rows = rows_of_slots(len(used), int(snap.get("n_dev", 1)))
    used_ix = np.flatnonzero(used)
    k = keys[used_ix]
    differing = len(k) - len(np.unique(k))      # an auction in two slots
    columns = set()
    for q in ref.alive:
        columns.add(q % ring)
        expected = np.zeros(len(used), np.int64)
        pc = ref.panes.get(q)
        if pc is not None:
            ix = k - pc.base
            ok = (ix >= 0) & (ix < len(pc.counts))
            expected[used_ix[ok]] = pc.counts[ix[ok]]
            named = np.flatnonzero(pc.counts) + pc.base
            differing += int((~np.isin(named, k)).sum())    # no slot
        differing += int(np.count_nonzero(
            counts[:, q % ring][rows] != expected))
    for col in set(range(ring)) - columns:      # dead or never written
        differing += int(np.count_nonzero(counts[:, col][rows]))
    return differing


def position_mismatches(meta: dict, snap: dict, ref: Reference) -> int:
    """The checkpoint's own account of where it was cut against the
    reference's: max timestamp, watermark, purge horizon."""
    (max_ts,) = meta["max_ts"].values()
    return (int(int(max_ts) != ref.max_ts)
            + int(int(snap["watermark"]) != ref.watermark)
            + int(int(snap["cleared_below"]) != ref.first_alive))


def holds_nothing(snap: dict) -> int:
    """Cells and keys an end-of-input checkpoint still holds (every
    window has fired by then)."""
    used = np.asarray(snap["directory"]["rev_used"], bool)
    rows = rows_of_slots(len(used), int(snap.get("n_dev", 1)))
    return int(used.sum()) + int(np.count_nonzero(
        np.asarray(snap["panes"]["counts"])[rows]))


def read_back(root: str, module, pool, schedule, batch: int, p: dict,
              wanted: int, shift: int = 0) -> dict:
    """(b) and (c) over the checkpoints under ``root``. ``shift`` moves
    the reference off the recorded position (the control)."""
    found = list_checkpoints(root)
    out = {"checkpoints_on_disk": [c[0] for c in found],
           "bytes_on_disk": [sum(
               os.path.getsize(os.path.join(d, f)) for f in os.listdir(d))
               for _cid, d, _m in found]}
    if len(found) < 2:
        # not even one in mid-stream and the last one: nothing to read
        return {**out, "completed_in_window": 0,
                "short": wanted, "cells_differing": -1,
                "position_mismatches": -1}
    last_meta, last_snap = load_checkpoint(found[-1][1], found[-1][2])
    completed = int(last_meta["metrics"].get("checkpoint.completed", 0))
    cid, d, manifest = found[-2]
    meta, snap = load_checkpoint(d, manifest)
    position = position_of(meta)
    t0 = time.perf_counter()
    ref = Reference(module, pool, schedule, batch, p, position + shift)
    out.update({
        "completed_in_window": completed,
        "short": max(0, wanted - completed),
        "checkpoint_id": cid, "position": position,
        "last_position": position_of(last_meta),
        "panes_alive": [ref.alive.start, ref.alive.stop - 1],
        "keys_in_directory": int(np.asarray(
            snap["directory"]["rev_used"], bool).sum()),
        "reference_batches": ref.batches,
        "cells_differing": cells_differing(snap, ref)
        + holds_nothing(last_snap),
        "position_mismatches": position_mismatches(meta, snap, ref),
        "reference_s": round(time.perf_counter() - t0, 3)})
    return out


def run(config, spec: dict, seed: int, rehearsal: bool, harness) -> dict:
    from flink_tpu.config import PipelineOptions

    out = float_sum_large_keys.run(config, spec, seed, rehearsal, harness)
    wanted = int(spec["checkpoints_wanted"])
    dirs = config.module.CHECKPOINT_DIRS
    root: Optional[str] = dirs[-1] if dirs else None
    batch = int(config.conf().get(PipelineOptions.MICROBATCH_SIZE))
    t0 = time.perf_counter()
    try:
        back = read_back(root, config.module,
                         config.module.make_pool(seed, batch, config.params),
                         config.schedule(), batch, config.params, wanted)
    finally:
        config.module.remove_checkpoints()
    back["seconds"] = round(time.perf_counter() - t0, 3)
    out["read_back"] = back
    out["compared"].update({
        f"checkpoints_short_of_{wanted}": [back["short"], 0],
        "checkpoint_cells_differing": [back["cells_differing"], 0],
        "checkpoint_position_mismatches": [back["position_mismatches"], 0]})
    out["holds"] = bool(out["holds"] and all(
        0 <= v <= lim for v, lim in (
            out["compared"][k] for k in (
                f"checkpoints_short_of_{wanted}",
                "checkpoint_cells_differing",
                "checkpoint_position_mismatches"))))
    return out
