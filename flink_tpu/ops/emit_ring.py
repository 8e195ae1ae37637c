"""How fired rows leave the device: the emit ring's host half.

A device operator's fires leave their rows in device memory and number
themselves (a ring VERSION per fire); a drain thread fetches what has
landed and turns rows into keys. This class is what both device
operators hold of that (``WindowOperator``'s top-n fires, which append
to ONE ring array whose every version holds all rows so far, and the
device session operator's, whose every fire pass fills a buffer of its
own): the lock the firing thread and the drain share, the version
counter, the announced versions (``copy_to_host_async`` issued, the
array kept), the version the drain read last, the fetch that never
parks behind a just-dispatched fire, and the fire cohorts on their way
from dispatch to delivery (the driver's ``trace.fires`` stamps). What
a row MEANS (its columns, slot -> key, window times) stays with the
operator that wrote it.

The driver's drain reads a ring in two steps: ``await_landing`` under
no lock (which version the poll will read is chosen there, once, and
the thread waits until that one has landed: a fired row leaves when
its copy is there, and the loop never finds a lock held for the
device's time), then the operator's ``drain_ring`` under the drain's
locks, where the read is local.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from flink_tpu.hostsync import landed, landing_wait, ready_wait


@dataclasses.dataclass
class Wanted:
    """What ``await_landing`` chose for the poll that follows it:
    version ``no`` in ``array`` (both None where every landed version
    is to be read, ``fetch_unread``, or nothing is), since ``t_want``,
    and ``t_landed``: when its wait ended."""

    no: Optional[int]
    array: Any
    t_want: float
    t_landed: float = 0.0


def await_arrays(arrays: List[Any], until: threading.Event, phases,
                 prof: Dict[str, float]) -> None:
    """The drain's wait for the rows of fires it holds markers of,
    under no lock, until they have landed or ``until`` is set. Counts
    the poll in ``prof``: ``drain_landed`` where the drain found them
    there, ``drain_waited`` where it had to wait (the detail
    ``drain/landing_wait``: a counter, as the drain's other waits)."""
    if landed(arrays):
        prof["drain_landed"] += 1
        return
    prof["drain_waited"] += 1
    with phases.detail("drain/landing_wait"):
        landing_wait(arrays, until)


# between two fires that carry rows a ring's array is announced (copied
# to the host) at most this often; the driver's drain lets a batch of
# markers without rows wait as long: no poll in between could read
# anything new
ANNOUNCE_INTERVAL_S = 0.05


class EmitRing:
    def __init__(self, keep: Optional[int] = 4,
                 announce_interval_s: float = ANNOUNCE_INTERVAL_S) -> None:
        # RLock: the spill+top-n sync path holds it across a fire and
        # its drain, and the fire's announce block takes it again
        # (ingest vs drain-thread deque race)
        self.lock = threading.RLock()
        # the device array the fires append to (an operator whose fires
        # share one array; lazily made: its shape needs the result
        # arity). ``None`` until then, and for an operator whose passes
        # each bring a buffer of their own.
        self.live: Any = None
        # recent ANNOUNCED versions as (version_no, array):
        # copy_to_host_async is issued at fire dispatch, and the array
        # is never donated, so every version stays valid. ``keep``
        # bounds the deque where every version holds all rows so far
        # (the newest landed one serves); ``None`` where each holds its
        # own rows and leaves the deque when read (``fetch_unread``).
        self.versions: collections.deque = collections.deque(maxlen=keep)
        self.version_no = 0
        # the version the drain fetched last: a periodic poll gains
        # nothing from that one or an older one (see fetch_version)
        self.read_no = 0
        # the drain's choice for its next periodic poll, between its
        # ``await_landing`` and that poll's read (``take_wanted``)
        self.wanted: Optional[Wanted] = None
        # fire-cohort bookkeeping (the driver's "trace.fires" records
        # and emit_latency_ms): a (ring_version, cohort) entry per
        # row-carrying fire, popped to ``delivered_stamps``, with the
        # fetch's own stamps, by the drain whose fetched version first
        # makes those rows HOST-VISIBLE. Both deques are bounded: in
        # modes where nothing pops them, old entries fall off — lost
        # samples, never lost rows.
        self.fire_stamps: collections.deque = collections.deque(maxlen=4096)
        self.delivered_stamps: collections.deque = collections.deque(
            maxlen=512)
        # device→host copies are stream ops with a fixed cost each: an
        # operator whose fires share one array announces it with every
        # fire that carries rows, and between those at a TIME cadence,
        # which it keeps here
        self.announce_interval_s = announce_interval_s
        self.last_announce = 0.0
        # every fire numbered up to this has had its rows decoded into
        # keys by the drain (the reuse rule's other half)
        self.fires_decoded = 0

    # -- the firing thread (under ``lock``) --------------------------------
    def announce(self, array: Any) -> None:
        """Start ``array``'s copy to the host and keep it as the
        current version."""
        for leaf in (array if isinstance(array, tuple) else (array,)):
            leaf.copy_to_host_async()
        self.versions.append((self.version_no, array))
        self.last_announce = time.perf_counter()

    def stamp(self, cohort: Dict[str, Any]) -> None:
        """``cohort``'s rows are in the current version."""
        self.fire_stamps.append((self.version_no, cohort))

    # -- the drain ----------------------------------------------------------
    def await_landing(self, need: int, until: threading.Event, phases,
                      prof: Dict[str, float]) -> None:
        """Ahead of a periodic poll, under NO lock but this ring's own
        for the choice: the drain holds markers of row-carrying fires
        up to version ``need`` (0: of none). Choose what the poll will
        read, once, and where rows are owed (no poll has read through
        ``need``) wait until they have landed or ``until`` is set. A
        marker without rows waits for nothing: its poll reads what has
        landed."""
        with self.lock:
            owed = need > self.read_no
            if self.versions.maxlen is None:
                # each version its own rows: every pass up to the
                # newest marker's; fetch_unread reads what has landed
                no, array = None, None
                arrays = [a for n, a in self.versions if n <= need]
            else:
                array, no = self.choose(need, True, landed_only=not owed)
                arrays = [array] if owed and array is not None else []
            self.wanted = wanted = Wanted(no, array, time.perf_counter())
        if arrays:
            await_arrays(arrays, until, phases, prof)
        wanted.t_landed = time.perf_counter()

    def take_wanted(self, opportunistic: bool) -> Optional[Wanted]:
        """(Under ``lock``.) The choice ``await_landing`` left for this
        poll; None where none was made, or the poll is a barrier's,
        which names its own version."""
        wanted, self.wanted = self.wanted, None
        return wanted if opportunistic else None

    def choose(self, need: int, opportunistic: bool,
               landed_only: bool = False
               ) -> Tuple[Optional[Any], Optional[int]]:
        """(Under ``lock``.) ``(array, its version)`` of the newest
        ANNOUNCED version >= ``need`` whose async copy already landed;
        where none has, the OLDEST such (the soonest), unless
        ``landed_only``; ``(None, None)`` where there is none. A version
        this drain has read already holds no row it has not seen: an
        opportunistic poll passes it over."""
        floor = max(need, self.read_no + 1) if opportunistic else need
        acceptable = [(no, arr_) for no, arr_ in self.versions
                      if no >= floor]
        for no, cand in reversed(acceptable):
            if cand.is_ready():
                return cand, no
        if acceptable and not landed_only:
            return acceptable[0][1], acceptable[0][0]
        return None, None

    def fetch_version(self, need: int, opportunistic: bool,
                      wanted: Optional[Wanted] = None
                      ) -> Tuple[Optional[np.ndarray], Optional[int],
                                 Optional[float]]:
        """(Under ``lock``.) ``(array, its version, when the wait for
        it ended)`` of ``wanted``'s version where the drain chose one
        ahead of this poll (it has landed: the read is local), else of
        ``choose``'s: never park behind the in-flight compute of a
        just-dispatched fire (a barrier's rows must be present, hence
        ``need``); ``(None, None, None)`` when an opportunistic poll
        finds nothing announced. Where only versions this drain has
        read have landed, a poll without ``wanted`` waits for the
        OLDEST it has not read instead of reading nothing: with a fire
        that outlasts the poll's arrival (~20 ms over 16.8 M rows) and
        the next poll a window's slide away, reading the version before
        it held the fired rows back by that slide."""
        if wanted is not None:
            target, no_read = wanted.array, wanted.no
        else:
            target, no_read = self.choose(need, opportunistic)
        if target is None:
            if opportunistic:
                # nothing announced (or, for the drain's own choice,
                # nothing landed that it has not read): fetch nothing
                return None, None, None
            # barrier needs a version newer than any announced copy:
            # announce the live array now so the fetch is a landed-copy
            # read, not an unannounced round trip
            target = self.live
            no_read = self.version_no
            self.announce(target)
        ready_wait(target)
        # the device's work and the copy are done: what is left of the
        # fetch is a local read
        t_ready = wanted.t_landed if wanted is not None \
            else time.perf_counter()
        self.read_no = max(self.read_no, no_read)
        return np.asarray(target), no_read, t_ready   # ONE round trip

    def fetch_unread(self, opportunistic: bool
                     ) -> Tuple[List[Any], Optional[int]]:
        """For versions that each hold rows of their own: ``(arrays
        oldest first, the version read through)`` of every announced
        version not read yet; an opportunistic poll stops before the
        first whose copy has not landed, unless that is the oldest (the
        soonest rows; as ``fetch_version``). They leave the deque; the
        caller waits for them outside the lock."""
        taken: List[Any] = []
        no_read = None
        while self.versions:
            no, arr = self.versions[0]
            if opportunistic and taken and not landed(arr):
                break
            self.versions.popleft()
            taken.append(arr)
            no_read = no
        if no_read is not None:
            self.read_no = max(self.read_no, no_read)
        return taken, no_read

    def deliver_stamps(self, no_read: int, t_fetch0: float,
                       t_ready: float, t_fetch1: float) -> None:
        """Every fire cohort at or below version ``no_read`` is
        host-visible as of the fetch that ran from ``t_fetch0`` to
        ``t_fetch1`` and whose wait for the device ended at
        ``t_ready``."""
        while self.fire_stamps and self.fire_stamps[0][0] <= no_read:
            cohort = self.fire_stamps.popleft()[1]
            cohort.update(t_fetch0=t_fetch0, t_ready=t_ready,
                          t_fetch1=t_fetch1)
            self.delivered_stamps.append(cohort)

    def take_delivered(self) -> List[Dict[str, Any]]:
        """Pop the fire cohorts whose rows became host-visible since
        the last call. The driver stamps ``t_sink`` on each and records
        one emit-latency sample per cohort at delivery time —
        host-visibility-accurate even when one drain poll coalesces
        many fires."""
        with self.lock:
            out = list(self.delivered_stamps)
            self.delivered_stamps.clear()
            return out

    def note_decoded(self, fire_no: int) -> None:
        """The drain has turned the rows of every fire up to
        ``fire_no`` into keys (called by it, after the decode)."""
        if fire_no > self.fires_decoded:
            self.fires_decoded = fire_no

    def pending_marker_no(self) -> int:
        """The version a barrier must read through, 0 where no fire
        has left rows behind."""
        if self.live is None and not self.versions:
            return 0
        return self.version_no

    def reset(self) -> None:
        """After a restore: everything the ring held was delivered
        before the snapshot; replay re-fires."""
        self.live = None
        self.versions.clear()
        self.wanted = None
        self.fire_stamps.clear()
        self.delivered_stamps.clear()
